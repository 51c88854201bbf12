"""Share of the HBM roofline reached by the window apply: the bytes the
apply needs (``bench/counts/ring_apply.py``: admitted rows read, weights
read and written) over the apply program's device time, against the
chip's published HBM bandwidth.  The apply moves no operations worth
counting, so bandwidth bounds it."""
from bench.readers import module_time

PROGRAMS = ("jit_apply",)


def read(data):
    n, t = module_time(data, PROGRAMS)
    if not n or t <= 0 or not data["peaks"]:
        return None
    need = data["counts"]["apply_bytes_per_window"] * n
    return 100.0 * need / t / data["peaks"]["hbm_bytes_per_s"]
