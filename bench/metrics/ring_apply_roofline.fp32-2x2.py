"""Share of one chip's HBM roofline reached by the window apply on the
mesh: the bytes the float32 apply needs (``bench/counts/ring_apply.py``:
admitted rows read, weights read and written) over the chips that share
each leaf along the model axis, times the executions of the apply program
(``jit_apply``: one event per chip per execution), over its device time
averaged over the chips, against the chip's published HBM bandwidth."""
from bench.readers import module_time

PROGRAMS = ("jit_apply",)


def read(data):
    n, t = module_time(data, PROGRAMS)
    if not n or t <= 0 or not data["peaks"]:
        return None
    runs = n / data["trace"]["devices"]
    per_chip = data["counts"]["apply_bytes_per_window"] \
        / data["spec"]["traffic"]["model_axis"]
    return 100.0 * per_chip * runs / t / data["peaks"]["hbm_bytes_per_s"]
