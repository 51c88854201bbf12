"""The Zamba2 cell's counts against a hand reckoning, the new cells' metric
readers on hand-made data, and both new cells end to end at smoke size on
the CPU (the four-chip mesh on four virtual CPU devices, in a child
process).  CPU only; nothing here describes a TPU."""
import copy
import json
import os
import subprocess
import sys
import types

import jax
import pytest

from bench import run, scope_trace, smoke
from bench.counts import zamba2 as counts
from bench.reference import ssm_lm
from bench.reference.zamba2 import Dims
from bench.weights_zamba2 import zamba2_weights

SEED = 2 ** 31 + 77
HYBRID = "zamba2-7b.personalize"
MESH = "mamba2-130m.personalize-fp32-2x2"

# d 4, d_inner 8 in 2 heads of 4, 2 groups of state 2 (in_proj 2*8 + 2*4
# + 2 = 26 wide, conv dim 16); 2 attention heads of 2 (width 4) over the 8-wide
# concat; MLP 6 wide; adapters of rank 3; layers 0-3 with hybrid layers 1
# and 3 (invocation 0 runs block A, invocation 1 block B)
TINY = Dims(d_model=4, n_layer=4, vocab=10, d_state=2, d_conv=3, expand=2,
            headdim=4, ngroups=2, eps=1e-5, attn_heads=2, attn_head_dim=2,
            d_ff=6, hybrid_ids=(1, 3, 5), n_blocks=2, rank=3,
            rope_theta=1e4)


def test_param_count_by_hand_and_by_tree():
    # Mamba2 layer: ln 4 + in_proj 4*26 + conv (3+1)*16 + 3*2 + gate 8
    #               + out_proj 8*4 = 218
    assert counts.mamba_layer_params(TINY) == 218
    # block: ln_in 8 + q/k/v 3*8*4 + o 4*4 + ln_mlp 4 + gate-up 4*12
    #        + down 6*4 = 196; invocation: linear 16 + adapter 3*(4+12)
    assert counts.block_params(TINY) == 196
    assert counts.invocation_params(TINY) == 16 + 48
    assert counts.adapter_params(TINY) == 2 * 48
    assert counts.n_params(TINY) == 2 * 40 + 4 + 4 * 218 + 2 * 196 + 2 * 64
    tree = jax.eval_shape(lambda: zamba2_weights(jax.random.PRNGKey(0),
                                                 TINY))
    assert sum(x.size for x in jax.tree.leaves(tree)) == \
        counts.n_params(TINY)


def test_flops_per_request_by_hand():
    L, K = 8, 5
    mamba = 2 * 4 * 26 + 2 * 8 * 4 + 2 * 3 * 16 + 4 * 2 * 4 * 2   # 432
    assert counts.mamba_forward_flops(TINY) == mamba
    core = 2 * (L + 1) * 2 * 2                                      # 72
    inv = 3 * 2 * 8 * 4 + core + 2 * 4 * 4 + (2 * 4 * 12 + 2 * 6 * 4) \
        + (2 * 4 * 3 + 2 * 3 * 12) + 2 * 16                         # 568
    assert counts.invocation_forward_flops(TINY, L) == inv
    head = 2 * 4 * 10
    assert counts.forward_flops(TINY, L) == 4 * mamba + 2 * inv + head
    # backward to the adapters: head and layers 1-3 (input gradients, the
    # scan twice), invocation 0 to its adapter, invocation 1 on to h
    grad = 2 * 3 * 12 + 2 * 12 * 3 + 2 * 4 * 3                      # 168
    first = 2 * 16 + 2 * 6 * 4 + grad
    second = first + (2 * 4 * 12 + 2 * 12 * 3 + 2 * 3 * 4) + 2 * 4 * 4 \
        + 2 * core + 3 * 2 * 8 * 4 // 2
    bwd = head + 3 * (mamba + 4 * 2 * 4 * 2) + first + second
    step = (3 * mamba + 2 * inv + head) + bwd
    assert counts.step_flops(TINY, L) == step
    conf = {"hidden_size": 4, "num_hidden_layers": 4, "vocab_size": 10,
            "mamba_d_state": 2, "mamba_d_conv": 3, "mamba_expand": 2,
            "mamba_headdim": 4, "mamba_ngroups": 2, "rms_norm_eps": 1e-5,
            "num_attention_heads": 2, "attention_head_dim": 2,
            "intermediate_size": 6, "hybrid_layer_ids": [1, 3, 5],
            "num_mem_blocks": 2, "adapter_rank": 3, "rope_theta": 1e4,
            "personalization": {"inner_steps": K}}
    # layer 0 lies before every adapter: once per request, the rest K times
    assert counts.flops_per_request(conf, {"stream_len": L}) == \
        L * (mamba + K * step)


def _data(trace=None, **kw):
    spec = {"traffic": {"model_axis": 2}, "root": "/nonexistent",
            "workload": HYBRID}
    return dict({"spec": spec, "window": {"completed": 8}, "trace": trace,
                 "peaks": {"bf16_flops_per_s": 100.0,
                           "hbm_bytes_per_s": 10.0},
                 "counts": {"flops_per_request": 50.0,
                            "apply_bytes_per_window": 40.0}}, **kw)


TRACE = {"window_s": 2.0, "busy_s": 1.5, "devices": 4,
         "modules": {"jit__lambda": [8, 0.4], "jit_apply": [8, 1.0]}}


@pytest.mark.parametrize("metric,want", [
    # 50 * 8 / 2 s over 4 chips of 100
    ("mfu.personalize-fp32-2x2", 100.0 * 200.0 / 400.0),
    # 2 runs (8 events over 4 chips) of 40 / 2 bytes in 1 s, peak 10
    ("ring_apply_roofline.fp32-2x2", 100.0 * 20.0 * 2 / 1.0 / 10.0),
    ("cohort_ms_per_req.zamba2-7b", 1e3 * 0.4 / 8),
])
def test_readers_on_hand_made_data(metric, want):
    read = run.load_module(f"{run.BENCH}/metrics/{metric}.py", "m").read
    assert read(_data(TRACE)) == pytest.approx(want)
    assert read(_data(None)) is None


def test_mfu_zamba2_on_hand_made_data():
    read = run.load_module(f"{run.BENCH}/metrics/mfu.zamba2-7b.personalize"
                           ".py", "m").read
    one = dict(TRACE, devices=1)
    assert read(_data(one)) == pytest.approx(100.0 * 200.0 / 100.0)
    assert read(_data(None)) is None


def _plane(name, lines, events, stats=None):
    meta = {k: types.SimpleNamespace(name=n, stats=s)
            for k, (n, s) in events.items()}
    return types.SimpleNamespace(
        name=name, event_metadata=meta, stat_metadata=stats or {},
        lines=[types.SimpleNamespace(
            name=ln, timestamp_ns=0,
            events=[types.SimpleNamespace(metadata_id=i, offset_ps=s * 1e3,
                                          duration_ps=d * 1e3)
                    for i, s, d in evs])
            for ln, evs in lines])


def _stat(text):
    return types.SimpleNamespace(str_value=text, ref_value=0)


def _planes(scoped=True):
    host = _plane("/host:CPU", [("t", [(1, 100, 1000)])],
                  {1: ("bench.window", [])})
    tag = "jit(f)/persafl.zamba2.shared/dot" if scoped else "jit(f)/dot"
    dev = _plane("/device:TPU:0",
                 [("XLA Modules", [(1, 0, 400), (1, 600, 300),
                                   (2, 950, 100)]),
                  ("XLA Ops", [(3, 150, 100), (3, 200, 100), (4, 300, 50),
                               (3, 650, 100), (3, 960, 50)])],
                 {1: ("jit__lambda(1)", []), 2: ("jit_apply(2)", []),
                  3: ("%fusion.1 = ...", [_stat(tag)]),
                  4: ("%fusion.2 = ...", [_stat("jit(f)/other")])})
    return [host, dev]


def test_scope_share_on_hand_made_planes():
    # the window is [100, 1100]; the cohort program runs [100, 400] and
    # [600, 900] there (600 ns); its scoped ops cover [150, 300] and
    # [650, 750] (250 ns); the scoped op at 960 lies in another program
    got = scope_trace.share_planes(_planes(), "persafl.zamba2.shared",
                                   ("jit__lambda",))
    assert got == pytest.approx(100.0 * 250 / 600)
    assert scope_trace.share_planes(_planes(scoped=False),
                                    "persafl.zamba2.shared",
                                    ("jit__lambda",)) is None


def test_scope_share_reads_nothing_without_a_trace():
    read = run.load_module(f"{run.BENCH}/metrics/shared_block_share."
                           "zamba2-7b.py", "m").read
    assert read(_data(None)) is None
    assert read(_data(dict(TRACE, devices=0))) is None
    assert read(_data(TRACE)) is None           # no trace file written


SMOKE_HYBRID = {"hidden_size": 256, "num_hidden_layers": 2,
                "vocab_size": 512, "traffic_vocab": 512, "mamba_d_state": 16,
                "mamba_headdim": 32, "chunk_size": 16,
                "num_attention_heads": 4, "num_key_value_heads": 4,
                "attention_head_dim": 32, "intermediate_size": 1024,
                "hybrid_layer_ids": [0, 1], "adapter_rank": 8,
                "compute_dtype": "float32"}


def hybrid_smoke_spec():
    """The Zamba2 cell at the program's ``reduce_for_smoke`` sizes (both
    layers hybrid, so both blocks and adapters run), with the smoke
    limits of ``bench/smoke.py``: the float32 program reads at most
    4.1e-7, 4.4e-11, 1.9e-7 and 7.4e-11 here (seed 2**31 + 77)."""
    spec = copy.deepcopy(run.cell_spec(HYBRID))
    conf = spec["config"]
    conf.update(SMOKE_HYBRID)
    conf["program"] = dict(conf["program"], smoke=True)
    conf["limits"] = {k: smoke.SMOKE_LIMITS[k] for k in conf["limits"]}
    spec["traffic"].update(smoke.SMOKE_MIX["personalize"])
    return spec


def mesh_smoke_spec():
    spec = copy.deepcopy(run.cell_spec(MESH))
    conf = spec["config"]
    conf.update(smoke.SMOKE_SIZES)
    conf["ssm_layer"] = dict(conf["ssm_layer"], **smoke.SMOKE_SSM)
    conf["program"] = dict(conf["program"], smoke=True)
    conf["limits"] = dict(smoke.SMOKE_LIMITS)
    spec["traffic"].update(smoke.SMOKE_MIX["personalize"])
    return spec


def test_hybrid_cell_runs_correct_on_cpu():
    res = run.run_cell(hybrid_smoke_spec(), SEED, 0.5, False,
                       require_chip=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"personalize_req_per_s",
                                   "personalize_p95_ms", "setup_s"}


def test_control_fails_the_hybrid_limits():
    spec = hybrid_smoke_spec()

    def hook(drv):
        drv.setup = lambda orig=drv.setup: (orig(), setattr(
            drv, "captured", list(drv.reference(ssm_lm.fp8))))[0]
    res = run.run_cell(spec, SEED, 0.5, False, require_chip=False,
                       driver_hook=hook)
    assert not res["correct"]


def test_program_config_refuses_a_file_that_differs():
    from bench.drivers.hybrid_personalize import program_config
    conf = hybrid_smoke_spec()["config"]
    program_config(conf)
    with pytest.raises(ValueError, match="sizes"):
        program_config(dict(conf, adapter_rank=16))


MESH_CHILD = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import run
from bench.test_bench_zamba2 import mesh_smoke_spec
res = run.run_cell(mesh_smoke_spec(), {seed}, 0.5, False, require_chip=False)
print(json.dumps({{"correct": res["correct"], "failed": res["failed"],
                  "devices": res["device"]["count"], "checks": res["checks"]}}))
"""


def test_mesh_cell_runs_correct_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_CHILD.format(root=run.ROOT, src=os.path.join(run.ROOT, "src"),
                             seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=run.ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] and res["devices"] == 4 and res["failed"] == 0, res
