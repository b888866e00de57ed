"""The ``dit_moe`` family and the registry's ``paper-dit-moe-xl``.

The program's dropless expert layer with its shared expert is compared
with the family's plain reference at a small size (d=64, 8 experts, top-2,
a shared expert of 32, 16 tokens), on seeded weights: the layer under a
router that sends most tokens to one expert, and the whole denoiser with
the gates renormalized and not.  The registry entry is served through the
CPU cell and checked correct; with the shared expert left out, or the
gates renormalized where the configuration says not, the same cell comes
out incorrect.  The family's FLOP and byte counts, the kernel roofline
reader and the configuration's model check are pinned beside them.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model, run
from chipbench.families import dit, dit_moe
from chipbench.reference import mm
from chipbench.tests import cpu_cell

SMALL = dict(cpu_cell.MODEL, seq_len=16, n_experts=8, top_k=2, shared_ff=32,
             norm_topk_prob=False)


def config(name: str = "dit-moe") -> dict:
    return json.loads((cpu_cell.ROOT / "chipbench" / "configs"
                       / f"{name}.json").read_text())


def small_cfg(**model_keys) -> dict:
    cfg = config()
    cfg["model"] = dict(SMALL, **model_keys)
    return cfg


def program(cfg: dict):
    """The program's denoiser config for ``cfg``, through the harness's own
    model check."""
    return run.denoiser_config(cfg, model.family(cfg))


# ------------------------------------------------------ against the reference


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_layer_matches_the_family_under_a_skewed_router():
    from repro.models.blocks import _maybe_ffn_fwd

    cfg = small_cfg()
    dc = program(cfg)
    w = model.make_weights(cfg)
    g = jax.tree_util.tree_map(lambda a: a[0], w["decoder"]["g0"])
    # tokens share a direction v, and expert 0's router column is v: most
    # tokens rank expert 0 first
    v = jax.random.normal(jax.random.PRNGKey(5), (64,))
    g["moe"]["router"] = g["moe"]["router"].at[:, 0].set(2.0 * v / 8.0)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (3, 16, 64)) + v
    h = dit.rmsnorm(x, g["ffn_norm"]["scale"], SMALL["norm_eps"])
    gates = dit_moe.gates(h, g["moe"]["router"], SMALL, mm)
    assert float(jnp.mean(jnp.argmax(gates, -1) == 0)) > 0.9
    got, _ = _maybe_ffn_fwd(g, x, dc.backbone, dc.backbone.group[0])
    want = x + dit_moe.experts(h, g, SMALL, mm)
    # float32 on both sides, the reference at HIGHEST: rounding of sums
    # taken in another order, a few ulps
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("renorm", [False, True])
def test_denoiser_matches_the_family(renorm):
    from repro.models.diffusion import denoiser_fwd

    cfg = small_cfg(norm_topk_prob=renorm)
    dc = program(cfg)
    assert dc.backbone.norm_topk_prob is renorm
    w = model.make_weights(cfg)
    t = jnp.asarray([3.0, 40.0, 90.0])
    y = jax.random.normal(jax.random.PRNGKey(11), (3, 16, 4))
    got = denoiser_fwd(w, t, y, dc)
    items = tuple(sorted(cfg["model"].items()))
    want = dit_moe.denoise(w, t, y, items)
    # float32 throughout: a few ulps; the other gate rule is far off
    assert rel(got, want) < 1e-5
    other = dit_moe.denoise(w, t, y, tuple(sorted(
        dict(cfg["model"], norm_topk_prob=not renorm).items())))
    assert rel(other, want) > 1e-2


# -------------------------------------------------------------- the CPU cell


def moe_cell() -> dict:
    c = cpu_cell.cell("dit-moe")
    c["cfg"].update(registry_model="paper-dit-moe-xl", model=dict(SMALL))
    return c


@pytest.fixture
def sound(monkeypatch):
    cpu_cell.peaks(monkeypatch)
    jax.clear_caches()  # no program traced before a fault is planted
    yield
    jax.clear_caches()


def test_registry_entry_is_served_correct(sound):
    out = cpu_cell.run_on_cpu(moe_cell())
    assert out["result"]["correct"], out["rows"]
    assert out["numbers"]["chains"] > 0
    assert "x0_rel_err" in out["numbers"], out["numbers"]


def leave_out_shared_expert(monkeypatch):
    import repro.models.blocks as blocks

    apply = blocks.ffn_lib.ffn_apply

    def dense_only(params, x, **kw):
        if params["w_gate"].shape[-1] == SMALL["shared_ff"]:
            return jnp.zeros_like(x)  # the shared expert's product
        return apply(params, x, **kw)

    monkeypatch.setattr(blocks.ffn_lib, "ffn_apply", dense_only)


def renormalize_gates(monkeypatch):
    build = run.denoiser_config

    def renormalized(cfg, fam):
        dc = build(cfg, fam)
        return dataclasses.replace(dc, backbone=dataclasses.replace(
            dc.backbone, norm_topk_prob=True))

    monkeypatch.setattr(run, "denoiser_config", renormalized)


@pytest.mark.parametrize("fault", [leave_out_shared_expert,
                                   renormalize_gates])
def test_planted_faults_come_out_incorrect(sound, monkeypatch, fault):
    fault(monkeypatch)
    out = cpu_cell.run_on_cpu(moe_cell())
    assert out["numbers"]["chains"] > 0
    assert not out["result"]["correct"], out["rows"]


# ------------------------------------------------------------------ counts

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
        "head_dim": 4, "d_ff": 16, "seq_len": 3, "d_data": 2,
        "time_dim": 4, "n_experts": 4, "top_k": 2, "shared_ff": 8,
        "compute_dtype": "bfloat16", "param_dtype": "bfloat16"}


def test_flops_count_routed_experts_only():
    # beside the dense block's one MLP (3 x 8 x 16): the router (8 x 4),
    # one more routed expert (3 x 8 x 16) and the shared one (3 x 8 x 8),
    # for 2 FLOPs, 3 tokens and 2 layers
    more = 2 * 3 * 2 * (8 * 4 + 3 * 8 * 16 + 3 * 8 * 8)
    assert dit_moe.flops(TINY) == dit.flops(TINY) + more


def test_gmm_work_by_hand():
    w = dit_moe.gmm_work(TINY)
    rows = 3 * 2  # tokens x top_k
    assert w["flops_per_point"] == 2 * 3 * 2 * rows * 8 * 16
    assert w["row_bytes_per_point"] == 2 * rows * 3 * (8 + 16) * 2
    assert w["weight_bytes_per_call"] == 4 * 8 * 16 * 2


def test_published_widths():
    cfg = config()
    m = cfg["model"]
    # 655.5 GFLOP a point, the routed experts 70% of it
    assert abs(dit_moe.flops(m) - 655.5e9) / 655.5e9 < 0.005
    assert dit_moe.gmm_work(m)["flops_per_point"] / dit_moe.flops(m) \
        == pytest.approx(0.70, abs=0.01)
    dc = program(cfg)
    assert dc.backbone.capacity_factor is None and dc.seq_len == 256
    from repro.models.diffusion import denoiser_init
    from repro.nn.param import unbox

    tree = jax.eval_shape(lambda k: unbox(denoiser_init(k, dc)),
                          jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert 3.93e9 < n < 3.95e9  # 140.7M a layer x 28, no adaLN
    want = {p: (s, "bfloat16") for p, (s, _) in
            dit_moe.leaf_shapes(m).items()}
    got = {"/".join(str(getattr(k, "key", k)) for k in path):
           (tuple(x.shape), str(x.dtype))
           for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == want


def test_family_refuses_a_model_it_does_not_describe():
    departs = dit_moe.program_departs(program(config("ldm-dit")))
    assert "dropless: no expert capacity" in departs
    assert "a shared expert" in departs
    capacity = dataclasses.replace(program(config()).backbone,
                                   capacity_factor=1.25)
    assert dit_moe.program_departs(dataclasses.replace(
        program(config()), backbone=capacity)) == [
            "dropless: no expert capacity"]


# ------------------------------------------------------------- the reader


def roofline_run(op_s, op_n, evals=10):
    return {"cfg": config(), "work": {"evals": evals},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"op_s": op_s, "op_n": op_n}}


def test_gmm_roofline_reads_the_kernels_ops():
    read = run.load_reader("gmm_roofline.moe")
    w = dit_moe.gmm_work(config()["model"])
    calls = 280  # 10 points of 28 layers, 3 calls a layer, as two ops
    least = max(10 * w["flops_per_point"] / 197e12,
                (calls * w["weight_bytes_per_call"]
                 + 10 * w["row_bytes_per_point"]) / 819e9)
    ops = {"_gmm.3 = custom-call(...)": least, "gmm.4": least,
           "_fusion.12 = fusion(...)": 5.0}
    n = {"_gmm.3 = custom-call(...)": 140, "gmm.4": 140,
         "_fusion.12 = fusion(...)": 9}
    share = read(roofline_run(ops, n))
    assert share == pytest.approx(50.0)
    assert read(roofline_run({"_fusion.12": 5.0}, {"_fusion.12": 9})) is None
    assert read(roofline_run(ops, n, evals=0)) is None
    assert read({"cfg": config("ldm-dit"), "trace": {}}) is None
