"""Share of the traced window in which no operation ran on the device."""
from bench.readers import idle_share


def read(data):
    return idle_share(data)
