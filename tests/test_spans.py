"""The program's spans (``repro.spans``) and the stable names of its device
programs: a serving window and a decode call record every span at its
layer boundary, nested as the layers call each other, into a
``jax.profiler`` trace read back with ``ProfileData``; the programs of the
serving and decode paths compile under their own names; and the serving
CLI's ``--profile-dir`` writes such a trace."""
import collections
import glob
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import PersAFLConfig
from repro.core import quant
from repro.launch import serve
from repro.models import api
from repro.serving import PersonalizationServer
from repro.serving.server import heads_fp32
from repro.spans import PREFIX

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def _trace_spans(tdir):
    """(name without the prefix, start, end) of every program span."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(ev.name[len(PREFIX):], ev.start_ns,
             ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def _nesting(spans):
    """Counter of (enclosing span's name or None, span's name)."""
    out = collections.Counter()
    for i, (name, s, e) in enumerate(spans):
        around = [(e2 - s2, n2) for j, (n2, s2, e2) in enumerate(spans)
                  if j != i and s2 <= s and e <= e2]
        out[(min(around)[1] if around else None, name)] += 1
    return out


def _record(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        fn()
    return _trace_spans(str(tmp_path))


def _loss(p, b):
    logp = jax.nn.log_softmax(b["x"] @ p["w"] + p["b"])
    return -jnp.mean(jnp.sum(jax.nn.one_hot(b["y"], 4) * logp, -1))


def _batch(seed, n=8, d=5):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(n, d).astype(np.float32),
            "y": rng.randint(0, 4, n).astype(np.int32)}


def test_serving_window_records_each_layer_span(tmp_path):
    params = {"w": jnp.full((5, 4), 0.1), "b": jnp.zeros((4,))}
    pcfg = PersAFLConfig(option="C", lam=20.0, inner_steps=3,
                         inner_eta=0.05, beta=0.5)
    srv = PersonalizationServer(params, _loss, pcfg, modes=("C",),
                                delta_dtype="int8")
    users = ["u0", "u1", "u2"]

    def window():
        tickets = [srv.submit(u, _batch(i)) for i, u in enumerate(users)]
        srv.flush()
        jax.block_until_ready([srv.poll(t) for t in tickets])
        srv.advance_window()

    window()                                   # compiles every program
    got = _nesting(_record(tmp_path, window))
    assert got == {
        (None, "flush"): 1,
        ("flush", "batcher.drain"): 1,
        ("batcher.drain", "engine.stack"): 1,
        ("batcher.drain", "engine.cohort"): 1,
        ("flush", "ef.residual"): 1,
        ("flush", "ef.quantize"): 1,
        ("flush", "ring.admit"): 1,
        (None, "poll"): len(users),
        (None, "advance"): 1,
        ("advance", "flush"): 1,               # advance's own (empty) flush
        ("advance", "ring.apply"): 1,
        ("advance", "ring.demote"): 1,
    }


def test_decode_call_records_each_step(tmp_path):
    cfg = reduce_for_smoke(get_config("mamba2-130m"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    heads = jax.tree.map(lambda x: jnp.stack([x, x]), params)
    prompt = jnp.ones((2, 4), jnp.int32)
    gen_len = 3

    def decode():
        serve._decode_personalized(cfg, heads, prompt, 4 + gen_len, 4)

    decode()
    got = _nesting(_record(tmp_path, decode))
    assert got == {(None, "decode"): 1,
                   ("decode", "decode.cast"): 1,
                   ("decode", "decode.init"): 1,
                   ("decode", "decode.prefill"): 1,
                   ("decode", "decode.first_step"): 1,
                   ("decode", "decode.step"): gen_len - 1,
                   ("decode", "decode.collect"): 1}


def _stack(k=4, d=6):
    x = jnp.arange(k * d, dtype=jnp.float32).reshape(k, d) / 7.0
    return {"a": x, "b": x[:, :3] - 1.0}


def _programs():
    raw = _stack()
    q = quant.quantize_stack(raw)
    rows = jnp.asarray([0, 2], jnp.int32)
    snap = jax.tree.map(lambda x: x[0], raw)
    return {
        "ef_quantize": (quant.ef_quantize, (raw,)),
        "ef_quantize_res": (quant.ef_quantize_res, (raw, raw)),
        "head_rows": (quant.head_rows, (snap, q, rows)),
        "gather_rows": (quant.gather_rows, (q, rows)),
        "quantize_tree": (quant.quantize_tree, (snap,)),
        "dequantize_tree": (quant.dequantize_tree,
                            (quant.quantize_tree(snap),)),
        "heads_fp32": (heads_fp32, (snap, raw)),
    }


@pytest.mark.parametrize("name", ["ef_quantize", "ef_quantize_res",
                                  "head_rows", "gather_rows",
                                  "quantize_tree", "dequantize_tree",
                                  "heads_fp32"])
def test_program_compiles_under_its_own_name(name):
    fn, args = _programs()[name]
    text = fn.lower(*args).as_text()
    assert f"module @jit_{name} " in text


@pytest.mark.parametrize("opener", ["TraceAnnotation", "named_scope"])
def test_only_the_span_helper_opens_trace_annotations(opener):
    users = [p.relative_to(SRC).as_posix() for p in SRC.rglob("*.py")
             if opener in p.read_text()]
    assert users == ["repro/spans.py"]


def test_serve_cli_profile_dir_writes_decode_spans(tmp_path, monkeypatch):
    # leave the persistent compile cache where this process has it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    prof = tmp_path / "prof"
    serve.main(["--arch", "mamba2-130m", "--smoke", "--requests", "2",
                "--tokens", "2", "--prompt-len", "4",
                "--out", str(tmp_path / "out"),
                "--profile-dir", str(prof)])
    names = collections.Counter(n for n, _, _ in _trace_spans(str(prof)))
    assert names["decode"] == 1 and names["decode.first_step"] == 1
