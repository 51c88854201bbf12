import os
import pathlib
import subprocess
import sys

import pytest

# tests see the real single CPU device; a test that needs several starts a
# child process with a forced host-device split
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def run_cli(tmp_path):
    """Run ``python *args`` as a child process and return its stdout.

    The child starts in an empty directory, so what a command writes
    relative to its working directory never lands in the checkout, and
    finds ``repro`` through ``PYTHONPATH``; ``devices`` gives it that many
    forced CPU host devices. A non-zero exit fails the test, and so does
    a run past ``timeout`` seconds, which kills it."""
    def run(args, *, timeout, env=None, devices=None):
        child_env = dict(os.environ)
        child_env["PYTHONPATH"] = os.pathsep.join(
            filter(None, [str(REPO / "src"), os.environ.get("PYTHONPATH")]))
        if devices is not None:
            child_env["JAX_PLATFORMS"] = "cpu"
            child_env["XLA_FLAGS"] = " ".join(filter(None, [
                os.environ.get("XLA_FLAGS"),
                f"--xla_force_host_platform_device_count={devices}"]))
        child_env.update(env or {})
        res = subprocess.run([sys.executable, *args], cwd=tmp_path,
                             env=child_env, capture_output=True, text=True,
                             timeout=timeout)
        assert res.returncode == 0, res.stdout[-4000:] + res.stderr[-4000:]
        return res.stdout
    return run


@pytest.fixture
def run_example(run_cli):
    """Run ``examples/<name>`` at its smoke size (``EXAMPLES_SMOKE=1``).

    A DeprecationWarning raised from the example itself or from ``repro``
    is an error, so an example that falls back on a retired spelling
    fails; deprecations from third-party packages stay warnings."""
    def run(name, *, timeout):
        return run_cli(["-W", "error::DeprecationWarning:__main__",
                        "-W", "error::DeprecationWarning:repro",
                        str(REPO / "examples" / name)],
                       timeout=timeout, env={"EXAMPLES_SMOKE": "1"})
    return run
