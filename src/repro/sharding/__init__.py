from repro.sharding.ctx import (activation_sharding,   # noqa: F401
                                shard_activation)
