"""arch id -> (config, model builder)."""
from __future__ import annotations

import importlib

from repro.configs.base import ArchConfig

ARCH_IDS = [
    "mamba2_370m",
    "recurrentgemma_2b",
    "qwen25_32b",
    "phi4_mini_3_8b",
    "nemotron4_15b",
    "codeqwen15_7b",
    "deepseek_v2_236b",
    "olmoe_1b_7b",
    "musicgen_large",
    "qwen2_vl_2b",
]

# CLI ids use dashes matching the assignment table
ALIASES = {
    "mamba2-370m": "mamba2_370m",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "qwen2.5-32b": "qwen25_32b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "nemotron-4-15b": "nemotron4_15b",
    "codeqwen1.5-7b": "codeqwen15_7b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "musicgen-large": "musicgen_large",
    "qwen2-vl-2b": "qwen2_vl_2b",
}


#: one representative arch per serving family — the engine parity tests and
#: the serving benchmark/CI gate iterate over exactly these (smoke-sized)
FAMILY_SMOKE = {
    "transformer": "codeqwen1.5-7b",
    "moe": "deepseek-v2-236b",        # MLA latent cache + routed experts
    "griffin": "recurrentgemma-2b",   # ring-buffer KV + RG-LRU state
    "ssm": "mamba2-370m",             # conv + SSD state
}


def canonical(arch_id: str) -> str:
    return ALIASES.get(arch_id, arch_id)


def get_config(arch_id: str, smoke: bool = False) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(arch_id)}")
    return mod.smoke() if smoke else mod.CONFIG


def build_model(cfg: ArchConfig):
    if cfg.family == "ssm":
        from repro.models.ssm import Mamba2LM
        return Mamba2LM(cfg)
    if cfg.family == "hybrid":
        from repro.models.griffin import GriffinLM
        return GriffinLM(cfg)
    if cfg.family == "moe":
        from repro.models.moe import MoELM
        return MoELM(cfg)
    from repro.models.transformer import TransformerLM
    return TransformerLM(cfg)


def load(arch_id: str, smoke: bool = False):
    cfg = get_config(arch_id, smoke)
    return cfg, build_model(cfg)


def init_params(model, ccfg, seed: int = 0, mesh=None,
                tp_policy: str = "cascade"):
    """Random params drawn on the device directly in ``ccfg``'s format.

    One jitted ``model.init_params``: with ``ccfg.mode == "serve_fp4"`` every
    linear is quantized as it is drawn, so no dense tree is ever held. With
    ``mesh``, the outputs come out already placed as ``ServeEngine`` serves
    them under ``tp_policy``: each device draws only its own shards
    (partitionable threefry), and no device holds the whole tree."""
    import jax

    key = jax.random.PRNGKey(seed)
    fn = lambda k: model.init_params(k, ccfg)
    shardings = None
    if mesh is not None:
        from repro.distributed.sharding import serving_param_shardings
        tied = bool(getattr(model.cfg, "tie_embeddings", False))
        shardings = serving_param_shardings(jax.eval_shape(fn, key), mesh,
                                            tp_policy, tied_embed=tied)
    return jax.jit(fn, out_shardings=shardings)(key)
