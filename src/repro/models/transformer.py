"""Generic decoder-only transformer LM.

Covers the dense GQA/MHA families (qwen2.5-32b, phi4-mini, nemotron-4,
codeqwen1.5) and the modality-stub backbones (musicgen-large [audio],
qwen2-vl-2b [vlm] with M-RoPE). Layer params are stacked (L, ...) and the
stack is lax.scan'ed (HLO stays small for 64-layer archs; the roofline
harness corrects loop trip counts — see benchmarks/roofline.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cascade
from repro.core.cascade import CascadeConfig
from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain_residual
from repro.models import layers as L
from repro.models.cache_utils import (StackedCacheMixin, paged_rows_restore,
                                      paged_rows_snapshot, seq_rows_restore,
                                      seq_rows_snapshot, take_last_valid)


def _remat_policy(name: str):
    import jax as _jax
    return {
        "dots": _jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "none": _jax.checkpoint_policies.nothing_saveable,
        "save_all": _jax.checkpoint_policies.everything_saveable,
    }[name]


class TransformerLM(StackedCacheMixin):
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.attn_cfg = L.AttnConfig(
            d_model=cfg.d_model,
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim,
            qkv_bias=cfg.qkv_bias,
            rope_theta=cfg.rope_theta,
            rope_fraction=cfg.rope_fraction,
            window=cfg.window,
            mrope_sections=cfg.mrope_sections,
            q_chunk=cfg.q_chunk,
        )
        # windowed archs hold O(window) ring state, so the serving context
        # length is unbounded by the cache (engine admission checks this)
        self.unbounded_context = self.attn_cfg.window > 0

    # ------------------------------------------------------------------ init
    def _layer_init(self, key: jax.Array, ccfg: CascadeConfig) -> dict:
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "ln1": L.norm_init(cfg.d_model, cfg.norm_type),
            "attn": L.attn_init(k1, self.attn_cfg, ccfg),
            "ln2": L.norm_init(cfg.d_model, cfg.norm_type),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind, ccfg),
        }

    def init_params(self, key: jax.Array, ccfg: CascadeConfig) -> dict:
        cfg = self.cfg
        keys = jax.random.split(key, cfg.n_layers + 2)
        params = {
            # one layer at a time: a vmap would hold every layer's f32 draw
            # at once (32 x 4096 x 13440 x 4 B = 7 GB for one codeqwen leaf)
            "layers": jax.lax.map(lambda k: self._layer_init(k, ccfg), keys[: cfg.n_layers]),
            "final_norm": L.norm_init(cfg.d_model, cfg.norm_type),
        }
        if not cfg.input_embeds:
            params["embed"] = L.embed_init(keys[-2], cfg.vocab, cfg.d_model, dtype=ccfg.compute_dtype)
        head_width = cfg.vocab * max(1, cfg.n_codebooks)
        if cfg.tie_embeddings and not cfg.input_embeds:
            pass  # logits via embed.T
        else:
            params["lm_head"] = cascade.linear_init(keys[-1], cfg.d_model, head_width, ccfg)
        return params

    # --------------------------------------------------------------- forward
    def _embed(self, params: dict, batch: dict, ccfg: CascadeConfig) -> jax.Array:
        cfg = self.cfg
        if cfg.input_embeds:
            x = batch["inputs_embeds"].astype(ccfg.compute_dtype)
        else:
            x = L.embed_apply(params["embed"], batch["tokens"])
        if cfg.rope_fraction == 0.0:  # sinusoidal-position archs (musicgen)
            s = x.shape[1]
            pos0 = batch.get("pos_offset", 0)
            x = x + L.sinusoidal_positions(s, cfg.d_model, pos0)[None].astype(x.dtype)
        return x

    def _head(self, params: dict, x: jax.Array, ccfg: CascadeConfig) -> jax.Array:
        cfg = self.cfg
        x = L.norm_apply(params["final_norm"], x, cfg.norm_type)
        if cfg.tie_embeddings and not cfg.input_embeds:
            logits = jnp.dot(x.astype(ccfg.compute_dtype), params["embed"]["table"].T,
                             preferred_element_type=jnp.float32)
        else:
            logits = cascade.linear_apply(params["lm_head"], x, ccfg)
        if cfg.n_codebooks:
            b, s, _ = logits.shape
            logits = logits.reshape(b, s, cfg.n_codebooks, cfg.vocab)
        return logits.astype(jnp.float32)

    def _block(self, lp: dict, x: jax.Array, ccfg: CascadeConfig,
               positions, cache, mode: str, max_len: int | None = None,
               n_valid=None):
        cfg = self.cfg
        h, new_cache = L.attn_apply(
            lp["attn"], L.norm_apply(lp["ln1"], x, cfg.norm_type),
            self.attn_cfg, ccfg, positions=positions, cache=cache, mode=mode,
            max_len=max_len, n_valid=n_valid)
        x = x + h
        x = x + L.mlp_apply(lp["mlp"], L.norm_apply(lp["ln2"], x, cfg.norm_type),
                            cfg.mlp_kind, ccfg)
        return constrain_residual(x), new_cache

    def forward(self, params: dict, batch: dict, ccfg: CascadeConfig,
                remat: bool = False, remat_policy: str = "dots") -> jax.Array:
        """Full-sequence forward (train / no-cache eval)."""
        cfg = self.cfg
        x = self._embed(params, batch, ccfg)
        positions = batch.get("positions")

        def body(x, lp):
            y, _ = self._block(lp, x, ccfg, positions, None, "full")
            return y, None

        if remat:
            body = jax.checkpoint(body, policy=_remat_policy(remat_policy))
        if cfg.scan_layers:
            x, _ = lax.scan(body, x, params["layers"])
        else:
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                x, _ = body(x, lp)
        return self._head(params, x, ccfg)

    # --------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        one = lambda _: L.attn_cache_init(batch, max_len, self.attn_cfg, dtype)
        return {"layers": jax.vmap(one)(jnp.arange(cfg.n_layers))}

    # ------------------------------------------------------- paged cache API
    @property
    def paged_attention(self) -> bool:
        """Full-attention archs page; ring state is O(window) and per-slot
        (nothing to share), multi-codebook grids serve slot-wise anyway."""
        return self.attn_cfg.window == 0 and not self.cfg.n_codebooks

    def init_paged_cache(self, batch: int, num_pages: int, page_size: int,
                         dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        one = lambda _: L.attn_cache_init_paged(batch, num_pages, page_size,
                                                self.attn_cfg, dtype)
        return {"layers": jax.vmap(one)(jnp.arange(cfg.n_layers))}

    def paged_copy_page(self, cache: dict, src, dst) -> dict:
        """Copy physical page ``src`` to ``dst`` in every pool leaf — the
        device half of radix-cache copy-on-write (a partially matched
        shared page is cloned before the new stream writes into it)."""
        out = {}
        for name, buf in cache["layers"].items():
            out[name] = buf if name == "pos" else buf.at[:, dst].set(buf[:, src])
        return {"layers": out}

    def prefill(self, params: dict, batch: dict, ccfg: CascadeConfig,
                max_len: int | None = None):
        cfg = self.cfg
        x = self._embed(params, batch, ccfg)
        positions = batch.get("positions")

        def body(x, lp):
            y, c = self._block(lp, x, ccfg, positions, None, "prefill", max_len=max_len)
            return y, c

        x, caches = lax.scan(body, x, params["layers"])
        logits = self._head(params, x[:, -1:], ccfg)
        return logits, {"layers": caches}

    def decode_step(self, params: dict, batch: dict, cache: dict, ccfg: CascadeConfig):
        cfg = self.cfg
        x = self._embed(params, batch, ccfg)
        positions = batch.get("positions")
        bt = batch.get("block_table")

        def body(x, scanned):
            lp, c = scanned
            if bt is not None:
                c = dict(c, block_table=bt)
            y, nc = self._block(lp, x, ccfg, positions, c, "decode")
            return y, nc

        x, new_caches = lax.scan(body, x, (params["layers"], cache["layers"]))
        logits = self._head(params, x, ccfg)
        return logits, {"layers": new_caches}

    # ----------------------------------------- continuous batching cache API
    # ``stack_caches``/``cache_at``/``write_cache`` come from
    # StackedCacheMixin: the serving engine keeps ONE fixed-shape cache for
    # the whole slot grid and admits/retires requests as slot writes, so
    # batched decode never recompiles as traffic comes and goes.

    def prefill_extend(self, params: dict, batch: dict, cache: dict,
                       ccfg: CascadeConfig, n_valid=None, all_logits: bool = False):
        """Append a (possibly right-padded) token chunk to an existing cache.

        Chunked-prefill admission path: the chunk shape stays fixed so long
        prompts compile ONE extend kernel regardless of length; only the
        first ``n_valid`` tokens of the chunk are real (full attention:
        pad K/V lands mask-invalid above each row's position; ring buffers:
        pad writes are dropped). Returns logits for the last valid token,
        (B, 1, V) — or for every chunk position, (B, S, V), when
        ``all_logits`` is set (the speculative-decode verify pass).
        """
        x = self._embed(params, batch, ccfg)
        b, s, _ = x.shape
        nv = jnp.asarray(s if n_valid is None else n_valid, jnp.int32)
        bt = batch.get("block_table")

        def body(x, scanned):
            lp, c = scanned
            if bt is not None:
                c = dict(c, block_table=bt)
            y, nc = self._block(lp, x, ccfg, None, c, "extend", n_valid=nv)
            return y, nc

        x, new_caches = lax.scan(body, x, (params["layers"], cache["layers"]))
        logits = self._head(params, x if all_logits else take_last_valid(x, nv), ccfg)
        return logits, {"layers": new_caches}

    # --------------------------------------------------- speculative decode
    def spec_verify(self, params: dict, batch: dict, cache: dict,
                    ccfg: CascadeConfig):
        """Score a (B, 1+K) draft chunk in ONE extend pass: per-position
        logits (B, 1+K, V), the advanced cache, and a rewind checkpoint
        (the KV rows the chunk overwrites — for ring buffers those are live
        in-window entries that a rejection must restore)."""
        bt = batch.get("block_table")
        s = batch["tokens"].shape[1]
        if bt is not None:
            ckpt = {"layers": paged_rows_snapshot(cache["layers"], bt, s),
                    "block_table": bt}
        else:
            ckpt = {"layers": seq_rows_snapshot(cache["layers"], s)}
        logits, cache = self.prefill_extend(params, batch, cache, ccfg,
                                            all_logits=True)
        return logits, cache, ckpt

    def spec_rewind(self, cache: dict, ckpt: dict, keep) -> dict:
        """Per-slot rewind after a verify pass: the first ``keep[b]`` chunk
        tokens stay committed, the rejected suffix rows are restored and
        ``pos`` rewinds to ``pos0 + keep[b]``. Paged checkpoints carry the
        block table the verify wrote through; pages the chunk spilled into
        stay mapped (the host releases them at retire)."""
        bt = ckpt.get("block_table")
        if bt is not None:
            return {"layers": paged_rows_restore(cache["layers"],
                                                 ckpt["layers"], bt, keep)}
        return {"layers": seq_rows_restore(cache["layers"], ckpt["layers"], keep)}
