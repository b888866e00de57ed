"""Config registry: assigned archs + the paper's own models, --arch selection."""

from __future__ import annotations

from repro.configs.archs import ARCHS, SUBQUADRATIC
from repro.configs.base import (
    ALL_SHAPES,
    BlockDesc,
    InputShape,
    ModelConfig,
    reduced,
)
from repro.models.diffusion import DenoiserConfig


# ------------------------------------------------- the paper's own models


def paper_ldm_dit() -> DenoiserConfig:
    """Latent-diffusion stand-in for StableDiffusion-v2 (paper §6.1, Fig 2):
    DiT-XL-class transformer over 32x32 latent patch tokens."""
    backbone = ModelConfig(
        name="paper-ldm-dit", family="dense", n_layers=28, d_model=1152,
        n_heads=16, n_kv_heads=16, d_ff=4608, vocab_size=1,
        pos_embed="none", embed_inputs=False,
    )
    return DenoiserConfig(backbone=backbone, seq_len=1024, d_data=16)


def paper_dit_moe_xl() -> DenoiserConfig:
    """DiT-MoE-XL/2-8E2A (Fei et al., arXiv:2407.11633) over a 256x256
    latent: DiT-XL/2's trunk (28 x 1152, 16 heads of 72) with every MLP a
    dropless mixture of 8 SwiGLU experts of 4 x 1152, top-2 by a softmax
    gate left unnormalized, beside a shared SwiGLU expert of 2 x 1152;
    32x32x4 latent patches as 256 tokens of 16 channels.  Held in bfloat16:
    the 3.94B parameters do not fit one v5e chip in float32."""
    backbone = ModelConfig(
        name="paper-dit-moe-xl", family="moe", n_layers=28, d_model=1152,
        n_heads=16, n_kv_heads=16, head_dim=72, d_ff=4608, vocab_size=1,
        group=(BlockDesc("attn", moe=True),),
        n_experts=8, top_k=2, capacity_factor=None, norm_topk_prob=False,
        shared_ff=2304, pos_embed="none", embed_inputs=False,
        param_dtype="bfloat16",
    )
    return DenoiserConfig(backbone=backbone, seq_len=256, d_data=16)


def paper_pixel_dit() -> DenoiserConfig:
    """Pixel-space stand-in for the LSUN-Church DDPM (paper §6.1, Fig 4):
    256x256x3 images as 1024 8x8-patch tokens."""
    backbone = ModelConfig(
        name="paper-pixel-dit", family="dense", n_layers=24, d_model=1024,
        n_heads=16, n_kv_heads=16, d_ff=4096, vocab_size=1,
        pos_embed="none", embed_inputs=False,
    )
    return DenoiserConfig(backbone=backbone, seq_len=1024, d_data=192)


def paper_diffusion_policy(action_dim: int = 14) -> DenoiserConfig:
    """Robomimic-style diffusion policy (paper §6.2): denoises an action
    sequence of k=16 steps x action_dim (7 single-arm / 14 bi-manual)."""
    backbone = ModelConfig(
        name="paper-diffusion-policy", family="dense", n_layers=8, d_model=512,
        n_heads=8, n_kv_heads=8, d_ff=2048, vocab_size=1,
        pos_embed="none", embed_inputs=False,
    )
    return DenoiserConfig(backbone=backbone, seq_len=16, d_data=action_dim)


def paper_diffusion_policy_smoke(action_dim: int = 4) -> DenoiserConfig:
    """CI/demo-sized diffusion policy: same topology as
    ``paper-diffusion-policy`` at smoke dims.  Heads (4) and d_ff (128)
    divide a 2- or 4-way ``model`` axis, so this is the registry config the
    ``--model-shards`` serve smoke and the model-parallel example arm use."""
    backbone = ModelConfig(
        name="paper-diffusion-policy-smoke", family="dense", n_layers=2,
        d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=1,
        pos_embed="none", embed_inputs=False, compute_dtype="float32",
        remat=False,
    )
    return DenoiserConfig(backbone=backbone, seq_len=8, d_data=action_dim)


def qwen3_moe_a3b_smoke(action_dim: int = 4) -> DenoiserConfig:
    """CI/demo-sized qwen3-moe-30b-a3b-family denoiser: attention blocks
    with a token-choice top-k MoE FFN, at smoke dims.  Experts (8) and
    heads (4) divide a 2- or 4-way ``model`` axis and capacity_factor >=
    E/k guarantees no token drops, so this is the registry config the
    ``--expert-parallel`` serve smoke and the EP/SP bench arms use (the
    full-size config lives in repro.configs.archs as qwen3-moe-30b-a3b)."""
    backbone = ModelConfig(
        name="qwen3-moe-a3b-smoke", family="moe", n_layers=2,
        d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=1,
        group=(BlockDesc("attn", moe=True),),
        n_experts=8, top_k=2, capacity_factor=8.0,
        pos_embed="none", embed_inputs=False, compute_dtype="float32",
        remat=False,
    )
    return DenoiserConfig(backbone=backbone, seq_len=8, d_data=action_dim)


PAPER_MODELS = {
    "paper-ldm-dit": paper_ldm_dit,
    "paper-pixel-dit": paper_pixel_dit,
    "paper-dit-moe-xl": paper_dit_moe_xl,
    "paper-diffusion-policy": paper_diffusion_policy,
    "paper-diffusion-policy-smoke": paper_diffusion_policy_smoke,
    "qwen3-moe-a3b-smoke": qwen3_moe_a3b_smoke,
}


def get_config(name: str) -> ModelConfig:
    if name in ARCHS:
        return ARCHS[name]()
    raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")


def get_denoiser_config(name: str) -> DenoiserConfig:
    if name in PAPER_MODELS:
        return PAPER_MODELS[name]()
    raise KeyError(f"unknown paper model {name!r}; known: {sorted(PAPER_MODELS)}")


def shapes_for(name: str) -> list[InputShape]:
    """The assigned shape cells for an arch, applying the brief's skip rules
    (long_500k only for sub-quadratic archs; all archs are decoders so
    decode shapes always run)."""
    out = []
    for shape in ALL_SHAPES:
        if shape.name == "long_500k" and name not in SUBQUADRATIC:
            continue
        out.append(shape)
    return out


def all_cells():
    """Every (arch, shape) dry-run cell, including noted skips."""
    cells = []
    for name in ARCHS:
        for shape in ALL_SHAPES:
            skipped = shape.name == "long_500k" and name not in SUBQUADRATIC
            cells.append((name, shape, skipped))
    return cells
