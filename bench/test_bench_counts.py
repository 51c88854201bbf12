"""Operation and byte counts against hand-worked values, and the peaks
table.  CPU only; nothing here describes a TPU."""
import jax
import pytest

from bench import run
from bench.counts import decode_step, personalize_step, ring_apply, ssm_lm
from bench.reference.ssm_lm import Dims
from bench.weights import mamba2_weights

# d_inner 8, 2 heads of 4, in_proj width 2*8 + 2*2 + 2 = 22, conv dim 12
TINY = Dims(d_model=4, n_layer=2, vocab=10, d_state=2, d_conv=3, expand=2,
            headdim=4, ngroups=1, eps=1e-6)


def conf(subset=None, codec="int8", steps=5):
    return {"d_model": 4, "n_layer": 2, "vocab_size": 10,
            "layer_norm_epsilon": 1e-6,
            "ssm_layer": {"d_state": 2, "d_conv": 3, "expand": 2,
                          "headdim": 4, "ngroups": 1},
            "personalization": {"inner_steps": steps},
            "serving": {"personal_subset": subset, "delta_dtype": codec}}


def test_param_count_by_hand_and_by_tree():
    # per layer: ln 4 + in_proj 4*22 + conv (3+1)*12 + 3*2 + gate 8
    #            + out_proj 8*4 = 186; embed 2*10*4; final norm 4
    assert ssm_lm.layer_params(TINY) == 186
    assert ssm_lm.n_params(TINY) == 80 + 4 + 2 * 186
    tree = jax.eval_shape(lambda: mamba2_weights(jax.random.PRNGKey(0),
                                                 TINY))
    leaves = jax.tree.leaves(tree)
    assert sum(x.size for x in leaves) == ssm_lm.n_params(TINY)
    assert len(leaves) == ssm_lm.n_leaves()


def test_forward_flops_by_hand():
    # per layer 2*4*22 + 2*8*4 + 2*3*12 + 4*2*4*2 = 376; head 2*4*10
    assert ssm_lm.backbone_forward_flops(TINY) == 2 * 376
    assert ssm_lm.head_forward_flops(TINY) == 80
    assert ssm_lm.forward_flops(TINY) == 832


@pytest.mark.parametrize("subset,want", [
    (None, 3 * 832 * 16 * 5),
    ("embed/unembed", 752 * 16 + 2 * 80 * 16 * 5),
])
def test_personalize_flops_by_hand(subset, want):
    assert personalize_step.flops_per_request(
        conf(subset), {"stream_len": 16}) == want


def test_head_only_backbone_does_not_grow_with_steps():
    mix = {"stream_len": 16}
    f = [personalize_step.flops_per_request(conf("embed/unembed",
                                                 steps=k), mix)
         for k in (1, 2, 7)]
    head_step = 2 * ssm_lm.head_forward_flops(TINY) * 16
    assert f[1] - f[0] == head_step and f[2] - f[1] == 5 * head_step
    full = [personalize_step.flops_per_request(conf(steps=k), mix)
            for k in (1, 2)]
    assert full[1] - full[0] == 3 * 832 * 16


@pytest.mark.parametrize("codec,want", [
    ("int8", 4 * (456 + 4 * 12) + 8 * 456),
    ("fp32", 4 * 4 * 456 + 8 * 456),
])
def test_ring_apply_bytes_by_hand(codec, want):
    assert ring_apply.bytes_per_window(conf(codec=codec), 4) == want


def test_decode_flops_by_hand():
    assert decode_step.flops_per_sequence(
        conf(), {"prompt_len": 6, "gen_len": 3}) == 832 * 8


def test_peaks_known_and_unknown_kind():
    v5e = run.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        run.load_peaks("cpu")
