"""The unified Model: init / train-forward / prefill / decode for every
architecture family, plus ShapeDtypeStruct input specs for the dry-run.

Caches are dicts: {"layers": [...per-layer...], "pos": int32 scalar} with an
extra "cross" list (encoder K/V) for encoder-decoder models.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core.tp import TPContext, constrain
from repro.models.attention import (
    KVCache, attention, attention_specs, init_attention,
    paged_attention_chunk, paged_attention_decode, paged_attention_mixed,
)
from repro.models.common import (
    Initializer, embed, init_norm, rms_norm, unembed,
)
from repro.models.mlp import init_mlp, mlp, mlp_specs
from repro.models.transformer import (
    apply_stack, init_stack, init_stack_cache, stack_specs,
)

__all__ = ["Model"]

AUX_WEIGHTS = {"load_balance": 1e-2, "router_z": 1e-3}


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.cfg
        init = Initializer(rng, jnp.dtype(cfg.dtype))
        p: Dict[str, Any] = {
            "embed": {"w": init.linear("embed", (cfg.vocab_size, cfg.d_model),
                                       scale=cfg.d_model**-0.5)},
            "layers": init_stack(init, cfg),
            "final_norm": init_norm(init, "final_norm", cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = {"w": init.linear("lm_head", (cfg.vocab_size, cfg.d_model))}
        if cfg.frontend == "vision":
            p["mm_proj"] = {"w": init.linear("mm_proj", (cfg.d_model, cfg.d_model))}
        if cfg.encoder_decoder:
            enc_cfg = cfg
            p["enc_layers"] = [
                {
                    "ln1": init_norm(init, f"enc{i}/ln1", cfg.d_model, cfg.norm),
                    "core": init_attention(init, f"enc{i}/attn", enc_cfg),
                    "ln2": init_norm(init, f"enc{i}/ln2", cfg.d_model, cfg.norm),
                    "mlp": init_mlp(init, f"enc{i}/mlp", enc_cfg),
                }
                for i in range(cfg.n_encoder_layers)
            ]
            p["enc_norm"] = init_norm(init, "enc_norm", cfg.d_model, cfg.norm)
            p["xattn"] = [
                {
                    "ln": init_norm(init, f"x{i}/ln", cfg.d_model, cfg.norm),
                    "core": init_attention(init, f"x{i}/attn", cfg),
                }
                for i in range(cfg.n_layers)
            ]
        return p

    def param_specs(self, ctx: TPContext):
        cfg = self.cfg
        a = ctx.axis if ctx.tp else None
        d = ctx.wdata
        p: Dict[str, Any] = {
            "embed": {"w": P(a, d)},
            "layers": stack_specs(cfg, ctx),
            "final_norm": {"w": P(None)},
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = {"w": P(a, d)}
        if cfg.frontend == "vision":
            p["mm_proj"] = {"w": P(d, a)}
        if cfg.encoder_decoder:
            enc_layer = {
                "ln1": {"w": P(None)},
                "core": attention_specs(cfg, ctx),
                "ln2": {"w": P(None)},
                "mlp": mlp_specs(cfg, ctx),
            }
            p["enc_layers"] = [enc_layer for _ in range(cfg.n_encoder_layers)]
            p["enc_norm"] = {"w": P(None)}
            p["xattn"] = [
                {"ln": {"w": P(None)}, "core": attention_specs(cfg, ctx)}
                for _ in range(cfg.n_layers)
            ]
        return p

    # --------------------------------------------------------------- encoder

    def _encode(self, ctx: TPContext, params, frames: jnp.ndarray) -> jnp.ndarray:
        """Whisper-style bidirectional encoder over stub frame embeddings."""
        cfg = self.cfg
        x = frames
        pos0 = jnp.int32(0)
        for lp in params["enc_layers"]:
            h = rms_norm(x, lp["ln1"]["w"])
            out, _ = attention(ctx, lp["core"], h, cfg, pos=pos0, causal=False)
            x = x + out
            h = rms_norm(x, lp["ln2"]["w"])
            x = x + mlp(ctx, lp["mlp"], h, cfg)
        return rms_norm(x, params["enc_norm"]["w"])

    def _cross_kv(self, ctx: TPContext, params, enc_out: jnp.ndarray):
        """Precompute per-decoder-layer cross-attention K/V from encoder out."""
        cfg = self.cfg
        B, F, _ = enc_out.shape
        kvs = []
        for xp in params["xattn"]:
            k = jnp.einsum("bfd,de->bfe", enc_out, xp["core"]["wk"]["w"].astype(enc_out.dtype))
            v = jnp.einsum("bfd,de->bfe", enc_out, xp["core"]["wv"]["w"].astype(enc_out.dtype))
            kvs.append(KVCache(k=k, v=v))  # flat (B, F, kv_dim)
        return kvs

    # ----------------------------------------------------------- embeddings

    def _embed_inputs(self, ctx: TPContext, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        x = embed(ctx, params["embed"]["w"], batch["tokens"])
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        if cfg.frontend == "vision" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(x.dtype)
            pe = jnp.einsum("bpd,de->bpe", pe, params["mm_proj"]["w"].astype(x.dtype))
            x = jnp.concatenate([pe, x], axis=1)  # early fusion
        return x

    def _apply_cross(self, ctx, params, x, cross_kv):
        cfg = self.cfg
        if cross_kv is None:
            return x
        for i, xp in enumerate(params["xattn"]):
            h = rms_norm(x, xp["ln"]["w"])
            out, _ = attention(ctx, xp["core"], h, cfg, pos=jnp.int32(0),
                               cross_kv=cross_kv[i])
            x = x + out
        return x

    # ----------------------------------------------------------------- train

    def loss(self, ctx: TPContext, params, batch) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        x = self._embed_inputs(ctx, params, batch)
        cross_kv = None
        if cfg.encoder_decoder:
            enc_out = self._encode(ctx, params, batch["encoder_frames"])
            cross_kv = self._cross_kv(ctx, params, enc_out)

        pos = jnp.int32(0)
        if cross_kv is not None:
            # interleave cross-attn per layer for enc-dec: apply self stack
            # layer-by-layer with cross after each (whisper block order:
            # self-attn, cross-attn, mlp)
            x, aux = self._encdec_decoder(ctx, params, x, cross_kv)
        else:
            x, _, aux = apply_stack(ctx, cfg, params["layers"], x, pos=pos)
        x = rms_norm(x, params["final_norm"]["w"])

        if cfg.frontend == "vision":
            x = x[:, cfg.n_patches :]  # loss on text positions only

        head = params.get("lm_head", params["embed"])["w"]
        logits = unembed(ctx, x, head).astype(jnp.float32)
        targets = batch["targets"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        ce = jnp.mean(logz - tgt)
        total = ce
        metrics = {"ce": ce}
        for k, v in aux.items():
            w = AUX_WEIGHTS.get(k, 0.0)
            total = total + w * v / max(1, cfg.n_layers)
            metrics[k] = v
        metrics["loss"] = total
        return total, metrics

    def _encdec_decoder(self, ctx, params, x, cross_kv):
        cfg = self.cfg
        from repro.models.transformer import apply_layer

        aux_total: Dict[str, jnp.ndarray] = {}
        pos = jnp.int32(0)
        for i, spec in enumerate(cfg.layers):
            x, _, aux = apply_layer(ctx, cfg, spec, params["layers"][i], x, pos=pos)
            # cross-attention sublayer
            xp = params["xattn"][i]
            h = rms_norm(x, xp["ln"]["w"])
            out, _ = attention(ctx, xp["core"], h, cfg, pos=pos, cross_kv=cross_kv[i])
            x = x + out
            for k, v in aux.items():
                aux_total[k] = aux_total.get(k, 0.0) + v
        return x, aux_total

    # ----------------------------------------------------------------- serve

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cache = {
            "layers": init_stack_cache(self.cfg, batch, max_len, dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
        if self.cfg.encoder_decoder:
            cfg = self.cfg
            cache["cross"] = [
                KVCache(
                    k=jnp.zeros((batch, cfg.encoder_seq, cfg.kv_dim), dtype),
                    v=jnp.zeros((batch, cfg.encoder_seq, cfg.kv_dim), dtype),
                )
                for _ in range(cfg.n_layers)
            ]
        return cache

    def prefill(self, ctx: TPContext, params, batch, cache, *,
                last_index=None) -> Tuple[jnp.ndarray, Any]:
        """Process the prompt; returns (last-token logits (B, V), cache).

        last_index: position to read logits from (int32 scalar, traced OK).
        Defaults to the final position; the continuous-batching engine passes
        the last REAL token's index when prompts are right-padded to a
        length bucket (pads sit after it, so causal masking hides them).
        """
        cfg = self.cfg
        x = self._embed_inputs(ctx, params, batch)
        cross_kv = cache.get("cross")
        if cfg.encoder_decoder:
            enc_out = self._encode(ctx, params, batch["encoder_frames"])
            cross_kv = self._cross_kv(ctx, params, enc_out)

        pos = jnp.int32(0)
        if cfg.encoder_decoder:
            x, new_layer_caches = self._serve_encdec(
                ctx, params, x, cache["layers"], cross_kv, pos, decode=False)
        else:
            x, new_layer_caches, _ = apply_stack(
                ctx, cfg, params["layers"], x, pos=pos, caches=cache["layers"])
        if last_index is None:
            x = x[:, -1:, :]
        else:
            x = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
        x = rms_norm(x, params["final_norm"]["w"])
        head = params.get("lm_head", params["embed"])["w"]
        logits = unembed(ctx, x, head)[:, 0]
        prompt_len = batch["tokens"].shape[1] + (
            cfg.n_patches if cfg.frontend == "vision" else 0
        )
        new_cache = {"layers": new_layer_caches,
                     "pos": jnp.asarray(prompt_len, jnp.int32)}
        if cfg.encoder_decoder:
            new_cache["cross"] = cross_kv
        return logits, new_cache

    def decode_step(self, ctx: TPContext, params, tokens, cache) -> Tuple[jnp.ndarray, Any]:
        """One decode step: tokens (B, 1) -> (logits (B, V), cache)."""
        cfg = self.cfg
        x = embed(ctx, params["embed"]["w"], tokens)
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        pos = cache["pos"]
        if cfg.encoder_decoder:
            x, new_layer_caches = self._serve_encdec(
                ctx, params, x, cache["layers"], cache["cross"], pos, decode=True)
        else:
            x, new_layer_caches, _ = apply_stack(
                ctx, cfg, params["layers"], x, pos=pos, caches=cache["layers"],
                decode=True)
        x = rms_norm(x, params["final_norm"]["w"])
        head = params.get("lm_head", params["embed"])["w"]
        logits = unembed(ctx, x, head)[:, 0]
        new_cache = {**cache, "layers": new_layer_caches, "pos": pos + 1}
        return logits, new_cache

    def prefill_chunk(self, ctx: TPContext, params, tokens, state, table_row,
                      start, n_valid,
                      cache_spec=None) -> Tuple[jnp.ndarray, Any]:
        """Chunked prefill: process ``chunk_size`` tokens of ONE in-flight
        prompt against the paged cache (DESIGN.md §Chunked prefill).

        tokens (1, C) int32 — a fixed-size chunk of the prompt, right-padded;
        table_row (max_blocks,) int32 — the slot's block-table row;
        start / n_valid — int32 scalars (traced): position of tokens[0, 0]
        and the number of real (non-pad) tokens in this chunk.

        Each attention layer gathers the slot's already-written paged history
        and attends over it plus the current chunk, then appends the chunk's
        K/V directly into the pools (wire-quantized when ``cache_spec`` is
        quantized) — no dense full-prompt cache is ever materialized, and
        every shape is independent of prompt length, so the engine compiles
        this exactly once for a whole serving run. Requires a pure-attention
        decoder (recurrent layers would fold chunk pads into their state;
        the engine routes those archs through whole-prompt prefill).

        Returns (logits (1, V) at chunk index ``n_valid - 1``, new state).
        """
        from repro.models.moe import moe
        from repro.models.transformer import _has_mlp_sublayer

        cfg = self.cfg
        if cfg.encoder_decoder:
            raise ValueError(
                "prefill_chunk does not thread encoder cross-attention; "
                "encoder-decoder models use whole-prompt prefill")
        x = embed(ctx, params["embed"]["w"], tokens)
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        pools_k = list(state["pools_k"])
        pools_v = list(state["pools_v"])
        ai = 0
        for i, spec in enumerate(cfg.layers):
            if spec.kind != "attn":
                raise ValueError(
                    f"prefill_chunk requires a pure-attention stack; layer "
                    f"{i} is {spec.kind!r} (use whole-prompt prefill)")
            lp = params["layers"][i]
            h = rms_norm(x, lp["ln1"]["w"])
            out, pools_k[ai], pools_v[ai] = paged_attention_chunk(
                ctx, lp["core"], h, cfg, start=start, table_row=table_row,
                pool_k=pools_k[ai], pool_v=pools_v[ai], window=spec.window,
                cache_spec=cache_spec)
            ai += 1
            x = constrain(ctx, x + out, ctx.batch, None, None)
            if _has_mlp_sublayer(cfg, spec):
                h = rms_norm(x, lp["ln2"]["w"])
                if spec.moe:
                    out, _ = moe(ctx, lp["moe"], h, cfg)
                else:
                    out = mlp(ctx, lp["mlp"], h, cfg)
                x = constrain(ctx, x + out, ctx.batch, None, None)
        x = rms_norm(x, params["final_norm"]["w"])
        x = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        head = params.get("lm_head", params["embed"])["w"]
        logits = unembed(ctx, x, head)[:, 0]
        new_state = {**state, "pools_k": pools_k, "pools_v": pools_v}
        return logits, new_state

    def mixed_step(self, ctx: TPContext, params, tokens, state, slot_ids,
                   positions, valid, is_decode, slot_starts, tables,
                   sample_idx, cache_spec=None) -> Tuple[jnp.ndarray, Any]:
        """Unified mixed-batch token-budget step (DESIGN.md §Mixed step):
        several slots' prefill chunks PLUS one token per DECODING slot,
        flattened into one ``(1, token_budget)`` batch and run as a single
        program — the engine's whole per-step work in one dispatch.

        tokens (1, T) int32 — the flattened budget, right-padded;
        slot_ids / positions / valid / is_decode (T,) — per-token owning
        slot, sequence position, real-vs-pad flag, and decode-vs-prefill
        flag; slot_starts (n_slots,) int32 — each slot's pre-step write
        position (history boundary); tables (n_slots, max_blocks) int32;
        sample_idx (n_slots,) int32 — per slot, the flat index of the token
        whose logits that slot samples from (its decode token, or the last
        valid token of its prefill segment; 0/garbage for slots that don't
        sample this step).

        Per attention layer ``paged_attention_mixed`` reads each token's
        slot history from the paged pools (the jnp read once per tile of
        same-slot tokens), attends it together with the same-slot tokens
        of the current batch (split-path precision semantics preserved
        token class by token class), and appends all
        new K/V into the pools. Shapes depend only on
        ``(token_budget, n_slots, max_blocks)``, so the engine compiles
        this exactly once — one program dispatch per step where the split
        scheduler paid two (chunk + decode). Requires a pure-attention
        decoder, like ``prefill_chunk``; hybrid archs keep the split
        whole-prompt + batched-decode path.

        Returns (logits (n_slots, V) at ``sample_idx``, new state).
        """
        from repro.models.moe import moe
        from repro.models.transformer import _has_mlp_sublayer

        cfg = self.cfg
        if cfg.encoder_decoder:
            raise ValueError(
                "mixed_step does not thread encoder cross-attention; "
                "encoder-decoder models use whole-prompt prefill + "
                "decode_step_paged")
        x = embed(ctx, params["embed"]["w"], tokens)
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        pools_k = list(state["pools_k"])
        pools_v = list(state["pools_v"])
        ai = 0
        for i, spec in enumerate(cfg.layers):
            if spec.kind != "attn":
                raise ValueError(
                    f"mixed_step requires a pure-attention stack; layer "
                    f"{i} is {spec.kind!r} (use whole-prompt prefill + "
                    f"decode_step_paged)")
            lp = params["layers"][i]
            h = rms_norm(x, lp["ln1"]["w"])
            out, pools_k[ai], pools_v[ai] = paged_attention_mixed(
                ctx, lp["core"], h, cfg, positions=positions,
                slot_ids=slot_ids, slot_starts=slot_starts, valid=valid,
                is_decode=is_decode, tables=tables, pool_k=pools_k[ai],
                pool_v=pools_v[ai], window=spec.window,
                cache_spec=cache_spec)
            ai += 1
            x = constrain(ctx, x + out, ctx.batch, None, None)
            if _has_mlp_sublayer(cfg, spec):
                h = rms_norm(x, lp["ln2"]["w"])
                if spec.moe:
                    out, _ = moe(ctx, lp["moe"], h, cfg)
                else:
                    out = mlp(ctx, lp["mlp"], h, cfg)
                x = constrain(ctx, x + out, ctx.batch, None, None)
        # logits only at each slot's sampled token: gather the n_slots rows
        # BEFORE the norm/unembed so the V-sized matmul stays O(n_slots),
        # not O(token_budget)
        x = x[0][sample_idx][:, None]                  # (n_slots, 1, d_model)
        x = rms_norm(x, params["final_norm"]["w"])
        head = params.get("lm_head", params["embed"])["w"]
        logits = unembed(ctx, x, head)[:, 0]
        new_state = {**state, "pools_k": pools_k, "pools_v": pools_v}
        return logits, new_state

    def decode_step_paged(self, ctx: TPContext, params, tokens, state,
                          tables, lengths,
                          cache_spec=None) -> Tuple[jnp.ndarray, Any]:
        """Continuous-batching decode: tokens (B, 1) over B slots with
        PER-SLOT positions against the paged KV cache (see
        serving/kv_cache.py and DESIGN.md §Decode step).

        state: pytree from ``init_paged_state`` (attention block pools,
        batched recurrent caches, optional per-slot encoder K/V);
        tables (B, max_blocks) int32; lengths (B,) int32 per-slot write
        positions; cache_spec: static KVCacheSpec — quantized pools are
        wire-format MXCompressed pairs (see DESIGN.md §Quantized cache).
        Shapes are independent of which slots are live, so this
        compiles exactly once regardless of request arrivals/departures.
        Returns (logits (B, V), new_state).
        """
        from repro.models.moe import moe
        from repro.models.transformer import _has_mlp_sublayer, apply_layer

        cfg = self.cfg
        x = embed(ctx, params["embed"]["w"], tokens)
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        pools_k = list(state["pools_k"])
        pools_v = list(state["pools_v"])
        rec = list(state["rec"])
        ai = ri = 0
        for i, spec in enumerate(cfg.layers):
            lp = params["layers"][i]
            if spec.kind == "attn":
                h = rms_norm(x, lp["ln1"]["w"])
                out, pools_k[ai], pools_v[ai] = paged_attention_decode(
                    ctx, lp["core"], h, cfg, lengths=lengths,
                    pool_k=pools_k[ai], pool_v=pools_v[ai], tables=tables,
                    window=spec.window, cache_spec=cache_spec)
                ai += 1
                x = constrain(ctx, x + out, ctx.batch, None, None)
                if _has_mlp_sublayer(cfg, spec):
                    h = rms_norm(x, lp["ln2"]["w"])
                    if spec.moe:
                        out, _ = moe(ctx, lp["moe"], h, cfg)
                    else:
                        out = mlp(ctx, lp["mlp"], h, cfg)
                    x = constrain(ctx, x + out, ctx.batch, None, None)
            else:
                # recurrent kinds are position-free: reuse the dense-layer
                # path with the slot-batched cache
                x, rec[ri], _ = apply_layer(ctx, cfg, spec, lp, x,
                                            pos=jnp.int32(0), cache=rec[ri],
                                            decode=True)
                ri += 1
            if cfg.encoder_decoder:
                xp = params["xattn"][i]
                h = rms_norm(x, xp["ln"]["w"])
                ck = KVCache(k=state["cross_k"][i], v=state["cross_v"][i])
                out, _ = attention(ctx, xp["core"], h, cfg, pos=jnp.int32(0),
                                   cross_kv=ck)
                x = x + out
        x = rms_norm(x, params["final_norm"]["w"])
        head = params.get("lm_head", params["embed"])["w"]
        logits = unembed(ctx, x, head)[:, 0]
        new_state = {**state, "pools_k": pools_k, "pools_v": pools_v, "rec": rec}
        return logits, new_state

    def _serve_encdec(self, ctx, params, x, layer_caches, cross_kv, pos, *, decode):
        cfg = self.cfg
        from repro.models.transformer import apply_layer

        new_caches = []
        for i, spec in enumerate(cfg.layers):
            x, c, _ = apply_layer(ctx, cfg, spec, params["layers"][i], x,
                                  pos=pos, cache=layer_caches[i], decode=decode)
            new_caches.append(c)
            xp = params["xattn"][i]
            h = rms_norm(x, xp["ln"]["w"])
            out, _ = attention(ctx, xp["core"], h, cfg, pos=pos, cross_kv=cross_kv[i])
            x = x + out
        return x, new_caches

    # ------------------------------------------------------------ input specs

    def input_specs(self, shape: InputShape, dtype=jnp.bfloat16) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32

        if shape.kind == "train":
            text = S - (cfg.n_patches if cfg.frontend == "vision" else 0)
            specs = {
                "tokens": jax.ShapeDtypeStruct((B, text), i32),
                "targets": jax.ShapeDtypeStruct((B, text), i32),
            }
            if cfg.frontend == "vision":
                specs["patch_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_patches, cfg.d_model), dtype)
            if cfg.encoder_decoder:
                specs["encoder_frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.encoder_seq, cfg.d_model), dtype)
        elif shape.kind == "prefill":
            text = S - (cfg.n_patches if cfg.frontend == "vision" else 0)
            specs = {"tokens": jax.ShapeDtypeStruct((B, text), i32)}
            if cfg.frontend == "vision":
                specs["patch_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_patches, cfg.d_model), dtype)
            if cfg.encoder_decoder:
                specs["encoder_frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.encoder_seq, cfg.d_model), dtype)
        else:  # decode
            specs = {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
        return specs
