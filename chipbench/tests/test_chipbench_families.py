"""Model families: what the harness knows of an architecture, found by the
configuration's ``reference`` key in ``chipbench/families/``.

The ``dit`` family's weights, forward pass and FLOPs are pinned to the
numbers the harness gave before families existed (the weights as sums per
leaf, the forward pass at a seeded input); a second architecture, the
registry's MoE smoke model, is served and checked correct from a family
file added to the directory and nothing else; a configuration naming no
family fails before an engine is built.
"""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model
from chipbench.families import dit
from chipbench.tests import cpu_cell

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
        "head_dim": 4, "d_ff": 16, "seq_len": 3, "d_data": 2,
        "time_dim": 4}


def test_denoiser_flops_tiny_by_hand():
    # per token and layer: q, k, v, o are 8x8 each (256 weights), gate, up
    # and down 8x16 each (384); in_proj 2x8 and out_proj 8x2 (32)
    per_token = 2 * (256 + 384) + 32
    attention = 2 * 4 * 3 * 3 * 8  # layers * 4 L^2 (heads * head_dim)
    time_embed = 2 * (4 * 8 + 8 * 8)
    assert dit.flops(TINY) == 2 * 3 * per_token + attention + time_embed


def test_denoiser_flops_grouped_kv():
    gqa = dict(TINY, n_kv_heads=1)  # k and v shrink to 8x4 each
    saved = 2 * 3 * 2 * (2 * (8 * 8 - 8 * 4))  # 2 FLOPs, 3 tokens, 2 layers
    assert dit.flops(TINY) - dit.flops(gqa) == saved


def test_ldm_dit_point_matches_the_published_estimate():
    m = {"n_layers": 28, "d_model": 1152, "n_heads": 16, "n_kv_heads": 16,
         "head_dim": 72, "d_ff": 4608, "seq_len": 1024, "d_data": 16,
         "time_dim": 256}
    # 2 * 596M weights * 1024 tokens + 4 * 1024^2 * 1152 * 28 ~ 1.36e12
    assert abs(dit.flops(m) - 1.36e12) / 1.36e12 < 0.01


def config(name: str) -> dict:
    return json.loads((cpu_cell.ROOT / "chipbench" / "configs"
                       / f"{name}.json").read_text())


def test_cells_count_the_flops_they_counted():
    # the FLOPs of a point behind mfu.*, as the dense formula gave them
    assert model.family(config("ldm-dit")).flops(
        config("ldm-dit")["model"]) == 1352993439744.0
    assert model.family(config("policy")).flops(
        config("policy")["model"]) == 168996864.0


# sum and sum of squares of each leaf, in float64, as the weight recipe
# gave them before families existed: the policy at its own size, ldm-dit
# cut to one layer
WEIGHT_SUMS = {
    "policy": {
        "decoder/g0/attn/wk": (-56.246337963262704, 2051.3001225399735),
        "decoder/g0/attn/wo": (-70.57340068478173, 2049.977092015297),
        "decoder/g0/attn/wq": (-69.37714420878982, 2047.827231841198),
        "decoder/g0/attn/wv": (-74.91419018829298, 2047.939781952653),
        "decoder/g0/attn_norm/scale": (0.0, 0.0),
        "decoder/g0/ffn/w_down": (87.49885731181529, 2053.18798329299),
        "decoder/g0/ffn/w_gate": (38.13621535759708, 8191.132192274996),
        "decoder/g0/ffn/w_up": (-46.20569085250855, 8196.655568690665),
        "decoder/g0/ffn_norm/scale": (0.0, 0.0),
        "final_norm/scale": (0.0, 0.0),
        "in_proj": (8.292709908444522, 257.8837682736509),
        "out_proj": (-0.1041715070139162, 0.013649416493566843),
        "t_mlp1": (23.9708113247072, 255.70639973515352),
        "t_mlp2": (14.83534252961158, 255.64618254192013),
    },
    "ldm-dit": {
        "decoder/g0/attn/wk": (-41.478439622047496, 1152.162607347505),
        "decoder/g0/attn/wo": (-67.52334422168097, 1152.1034324422267),
        "decoder/g0/attn/wq": (-49.10676643502589, 1150.9890682289808),
        "decoder/g0/attn/wv": (-66.76928384885024, 1151.9220058939184),
        "decoder/g0/attn_norm/scale": (0.0, 0.0),
        "decoder/g0/ffn/w_down": (29.59010147045415, 1152.3258421274859),
        "decoder/g0/ffn/w_gate": (57.66086168545098, 4607.799203917033),
        "decoder/g0/ffn/w_up": (-60.44648624801249, 4608.260634758667),
        "decoder/g0/ffn_norm/scale": (0.0, 0.0),
        "final_norm/scale": (0.0, 0.0),
        "in_proj": (16.879962144190813, 1156.371704013926),
        "out_proj": (0.11333057994402962, 0.01571142060449469),
        "t_mlp1": (41.26018945800351, 1152.3575511298855),
        "t_mlp2": (37.65275829422828, 1153.4141174269887),
    },
}


def flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        out.update(flat(v, p) if isinstance(v, dict) else {p: v})
    return out


@pytest.mark.parametrize("name", sorted(WEIGHT_SUMS))
def test_weights_are_the_recipe_they_were(name):
    cfg = config(name)
    if name == "ldm-dit":
        cfg["model"] = dict(cfg["model"], n_layers=1)
    got = {p: np.asarray(x, np.float64)
           for p, x in flat(model.make_weights(cfg)).items()}
    assert sorted(got) == sorted(WEIGHT_SUMS[name])
    for p, (s, q) in WEIGHT_SUMS[name].items():
        assert got[p].sum() == pytest.approx(s, rel=1e-6, abs=1e-9), p
        assert (got[p] ** 2).sum() == pytest.approx(q, rel=1e-6, abs=1e-9), p


PIN_MODEL = {"n_layers": 2, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 8, "d_ff": 64, "seq_len": 4, "d_data": 3,
             "time_dim": 16, "norm_eps": 1e-6, "compute_dtype": "float32",
             "param_dtype": "float32"}
# x0_hat (2, 4, 3) at t = (3, 17), y ~ N(0, 1) from PRNGKey(11), as the
# reference's dense forward pass gave it before families existed
DENOISED = {
    None: [1.29981697, 0.819385409, 0.642190754, 0.0407613814, -0.167446166,
           1.26491547, 0.0294244215, -1.31165147, 1.34127426, 0.504891276,
           -0.0891360044, -0.192763686, 0.196286917, -0.57372719,
           0.128917098, -0.124873877, -0.341497779, 0.778474927,
           -0.100579321, -0.16134122, 0.141123116, 0.288116992, 0.317215651,
           0.340191126],
    "bfloat16": [1.29900098, 0.815889955, 0.649253368, 0.0408392549,
                 -0.168148518, 1.27013946, 0.0246674865, -1.3169055,
                 1.3436892, 0.51071465, -0.0887930393, -0.189448774,
                 0.195567191, -0.572137952, 0.127407223, -0.121432841,
                 -0.334442765, 0.781307757, -0.09891361, -0.153498054,
                 0.140601754, 0.282750666, 0.323484421, 0.343233645],
}


@pytest.mark.parametrize("lower", [None, "bfloat16"])
def test_dit_denoise_is_the_reference_it_was(lower):
    cfg = {"name": "pin", "reference": "dit", "model": PIN_MODEL,
           "weights": {"seed": 5, "init": "lecun_normal", "zero": ["scale"],
                       "out_proj_scale": 1.0}}
    w = model.make_weights(cfg)
    y = jax.random.normal(jax.random.PRNGKey(11), (2, 4, 3))
    out = model.family(cfg).denoise(w, jnp.asarray([3.0, 17.0]), y,
                                    tuple(sorted(PIN_MODEL.items())), lower)
    want = np.asarray(DENOISED[lower])
    got = np.asarray(out, np.float64).ravel()
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-6


MOE_MODEL = dict(cpu_cell.MODEL, n_experts=8, top_k=2, capacity_factor=8.0)


@pytest.fixture
def moe_family(tmp_path, monkeypatch):
    """The family directory as a new architecture's change leaves it: a
    copy of the committed one with the MoE fixture's file added."""
    families = tmp_path / "families"
    shutil.copytree(model.FAMILIES, families)
    shutil.copy(cpu_cell.ROOT / "chipbench" / "tests" / "fixtures" / "moe.py",
                families / "moe.py")
    monkeypatch.setattr(model, "FAMILIES", families)
    cpu_cell.peaks(monkeypatch)
    jax.clear_caches()
    yield
    jax.clear_caches()


def moe_cell() -> dict:
    c = cpu_cell.cell()
    c["cfg"].update(registry_model="qwen3-moe-a3b-smoke", model=MOE_MODEL,
                    reference="moe")
    return c


def test_moe_family_from_a_new_file_is_served_correct(moe_family):
    out = cpu_cell.run_on_cpu(moe_cell())
    assert out["result"]["correct"], out["rows"]
    assert out["numbers"]["chains"] > 0
    assert "x0_rel_err" in out["numbers"], out["numbers"]
    # the experts count where routed, top_k = 2 of the 8: beside the dense
    # block's one MLP, the router's 64x8 weights and one more expert, for
    # 2 FLOPs, 8 tokens and 2 layers
    fam = model.family(moe_cell()["cfg"])
    assert fam.flops(MOE_MODEL) == dit.flops(MOE_MODEL) \
        + 2 * 8 * 2 * (64 * 8 + 3 * 64 * 128)


def test_moe_reference_matches_the_program_layer(moe_family):
    # the program's MoE layer, replicated, against the family's dropless one
    from repro.configs.registry import get_denoiser_config
    from repro.nn.moe import moe_apply
    from chipbench.reference import mm

    fam = model.family(moe_cell()["cfg"])
    w = model.make_weights(moe_cell()["cfg"])
    e = jax.tree_util.tree_map(lambda a: a[0], w["decoder"]["g0"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 64))
    got, _ = moe_apply(e, h, get_denoiser_config("qwen3-moe-a3b-smoke")
                       .backbone)
    want = fam.experts(h, e, MOE_MODEL, mm)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("change, refusal", [
    ({"model": {"n_expert": 8}}, "model keys \\['n_expert'\\] name no field"),
    ({"registry_model": "qwen3-moe-a3b-smoke"}, "dense full-attention blocks"),
])
def test_model_check_refuses_what_the_family_does_not_describe(change,
                                                                refusal):
    from chipbench import run

    cfg = config("policy")
    cfg["model"] = dict(cfg["model"], **change.pop("model", {}))
    cfg.update(change)
    with pytest.raises(SystemExit, match=refusal):
        run.denoiser_config(cfg, model.family(cfg))


def test_unknown_family_fails_before_the_engine_is_built(monkeypatch):
    import repro.launch.serve as serve

    def never(*a, **k):
        raise AssertionError("an engine was built")

    monkeypatch.setattr(serve, "build_engine", never)
    c = cpu_cell.cell()
    c["cfg"]["reference"] = "no-such-family"
    with pytest.raises(SystemExit, match="no model family 'no-such-family'"):
        cpu_cell.run_on_cpu(c)
