"""Model builder: init / train forward / prefill / decode for all families.

Layers live as *stacked* param pytrees scanned with ``lax.scan`` — one
compiled layer body regardless of depth (compile-time and remat-friendly;
the production choice).  Heterogeneous stacks (hybrid/ssm) scan over
*macro blocks* (the smallest repeating pattern), with any remainder layers
applied unscanned.

Param dtype is f32 master; compute casts to bf16 at the embedding.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nn import attention as A
from ..nn import attn_backend as AB
from ..nn import recurrent as R
from ..nn.attn_backend import PagedKV
from ..nn.common import dense_init, embed_init, rms_norm, split_keys
from ..nn.mlp import init_mlp, mlp_block
from ..nn.moe import init_moe, moe_block, moe_block_sparse
from .config import ArchConfig

Params = Dict[str, Any]
COMPUTE_DTYPE = jnp.bfloat16
MOE_AUX_WEIGHT = 0.01


# ---------------------------------------------------------------- windows
def layer_windows(cfg: ArchConfig, n: Optional[int] = None) -> np.ndarray:
    """Per-layer attention window (0 = global)."""
    n = n or cfg.n_layers
    if cfg.global_every:
        return np.array(
            [0 if (l + 1) % cfg.global_every == 0 else cfg.local_window
             for l in range(n)], np.int32)
    return np.full(n, cfg.local_window, np.int32)


def macro_pattern(cfg: ArchConfig) -> Tuple[Tuple[str, ...], int, int]:
    """(pattern, n_macro, n_tail) for heterogeneous stacks."""
    pat = cfg.block_pattern or ("attn",)
    return pat, cfg.n_layers // len(pat), cfg.n_layers % len(pat)


# ------------------------------------------------------------------ init
def _init_mixer(key, cfg: ArchConfig, kind: str) -> Params:
    if kind in ("attn", "attn_local"):
        p = A.init_attention(key, cfg.d_model, cfg.q_heads,
                             cfg.n_kv_heads, cfg.head_dim_,
                             cfg.qkv_bias, cfg.qk_norm)
        if cfg.q_heads != cfg.n_heads:  # zero pad heads: exactness
            cut = cfg.n_heads * cfg.head_dim_
            p["wq"] = p["wq"].at[:, cut:].set(0.0)
            p["wo"] = p["wo"].at[cut:, :].set(0.0)
        return p
    if kind == "rglru":
        return R.init_rglru(key, cfg.d_model, cfg.d_model)
    if kind == "mlstm":
        return R.init_mlstm(key, cfg.d_model, cfg.n_heads)
    if kind == "slstm":
        return R.init_slstm(key, cfg.d_model, cfg.n_heads)
    raise ValueError(kind)


def _init_layer(key, cfg: ArchConfig, kind: str) -> Params:
    k = split_keys(key, 3)
    p: Params = {
        "mixer": _init_mixer(k[0], cfg, kind),
        "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if cfg.d_ff > 0:
        p["ln2"] = jnp.zeros((cfg.d_model,), jnp.float32)
        if cfg.n_experts:
            p["moe"] = init_moe(k[1], cfg.d_model, cfg.d_ff,
                                cfg.n_experts_padded, cfg.n_shared_experts,
                                cfg.shared_d_ff)
        else:
            p["mlp"] = init_mlp(k[1], cfg.d_model, cfg.d_ff)
    return p


def _stack_layers(key, cfg: ArchConfig, kind: str, n: int) -> Params:
    """``n`` layers' params stacked on a leading axis.  One layer is
    traced and vmapped over the split keys — the same values as
    initializing each layer and stacking, in one layer's program (a
    jitted full-depth init compiles in seconds, not minutes)."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _init_layer(k, cfg, kind))(keys)


def init_params(cfg: ArchConfig, key) -> Params:
    k = split_keys(key, 8)
    Vp, D = cfg.vocab_padded, cfg.d_model
    params: Params = {
        "embed": embed_init(k[0], (Vp, D)),
        "head": dense_init(k[1], (D, Vp)),
        "ln_f": jnp.zeros((D,), jnp.float32),
    }
    pat, n_macro, n_tail = macro_pattern(cfg)
    if cfg.block_pattern:
        params["macros"] = {
            f"m{i}_{kind}": _stack_layers(
                jax.random.fold_in(k[2], i), cfg, kind, n_macro)
            for i, kind in enumerate(pat)
        }
        params["tail"] = [
            _init_layer(jax.random.fold_in(k[3], i), cfg, pat[i])
            for i in range(n_tail)
        ]
    else:
        params["layers"] = _stack_layers(k[2], cfg, "attn", cfg.n_layers)
    if cfg.n_encoder_layers:
        params["enc_layers"] = _stack_layers(k[4], cfg, "attn",
                                             cfg.n_encoder_layers)
        params["enc_ln_f"] = jnp.zeros((D,), jnp.float32)
        params["cross_layers"] = _stack_layers(k[5], cfg, "attn",
                                               cfg.n_layers)
    if cfg.frontend:
        params["frontend_proj"] = dense_init(k[6], (cfg.frontend_dim, D))
    return params


# --------------------------------------------------------------- forward
def _ffn(p: Params, cfg: ArchConfig, x, moe_impl: str):
    if cfg.d_ff == 0:
        return x, 0.0
    # named scope: the profiler's name stack of every op below (the
    # benchmark's mlp_share / moe_share read it)
    with jax.named_scope("moe" if cfg.n_experts else "mlp"):
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            fn = moe_block_sparse if moe_impl == "sparse" else moe_block
            out, aux = fn(p["moe"], h, n_experts=cfg.n_experts,
                          top_k=cfg.n_experts_active, act=cfg.act)
            return x + out, aux
        return x + mlp_block(p["mlp"], h, cfg.act), 0.0


def _mixer_fwd(p: Params, cfg: ArchConfig, kind: str, x, window,
               positions, q_block: int, mlstm_chunk: int = 0):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("attn", "attn_local"):
        out = A.attention_block(
            p["mixer"], h, n_heads=cfg.q_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim_, rope_theta=cfg.rope_theta, window=window,
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps, positions=positions,
            q_block=q_block)
    elif kind == "rglru":
        out = R.rglru_block(p["mixer"], h)
    elif kind == "mlstm":
        out = R.mlstm_block(p["mixer"], h, cfg.n_heads,
                            chunk=mlstm_chunk or R.MLSTM_CHUNK)
    elif kind == "slstm":
        out = R.slstm_block(p["mixer"], h, cfg.n_heads)
    else:
        raise ValueError(kind)
    return x + out


def _remat(body, remat_policy: str):
    """Remat wrapper: 'full' recomputes everything in backward (min
    memory, max recompute bytes); 'dots' saves matmul outputs (the
    §Perf memory-term lever); 'none' disables remat."""
    if remat_policy == "none":
        return body
    if remat_policy == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def _dense_stack(params_stacked, cfg: ArchConfig, x, windows, positions,
                 moe_impl: str, q_block: int, remat: bool = True,
                 unroll: bool = False, mlstm_chunk: int = 0,
                 remat_policy: str = "full"):
    """Scan over stacked homogeneous attention layers."""

    def body(carry, xs):
        x, aux = carry
        lp, window = xs
        x = _mixer_fwd(lp, cfg, "attn", x, window, positions, q_block,
                       mlstm_chunk)
        x, a = _ffn(lp, cfg, x, moe_impl)
        return (x, aux + a), None

    fn = _remat(body, remat_policy) if remat else body
    (x, aux), _ = jax.lax.scan(fn, (x, 0.0),
                               (params_stacked, jnp.asarray(windows)),
                               unroll=unroll)
    return x, aux


def _macro_stack(params, cfg: ArchConfig, x, positions, moe_impl: str,
                 q_block: int, remat: bool = True, unroll: bool = False,
                 mlstm_chunk: int = 0, remat_policy: str = "full"):
    """Scan over heterogeneous macro blocks, then remainder layers."""
    pat, n_macro, n_tail = macro_pattern(cfg)
    windows = jnp.full((n_macro,), cfg.local_window, jnp.int32)

    def body(carry, xs):
        x, aux = carry
        for i, kind in enumerate(pat):
            lp = xs[f"m{i}_{kind}"]
            x = _mixer_fwd(lp, cfg, kind, x, xs["window"], positions,
                           q_block, mlstm_chunk)
            x, a = _ffn(lp, cfg, x, moe_impl)
            aux = aux + a
        return (x, aux), None

    xs = dict(params["macros"])
    xs["window"] = windows
    fn = _remat(body, remat_policy) if remat else body
    (x, aux), _ = jax.lax.scan(fn, (x, 0.0), xs, unroll=unroll)
    for i, lp in enumerate(params["tail"]):
        kind = pat[i]
        x = _mixer_fwd(lp, cfg, kind, x, jnp.int32(cfg.local_window),
                       positions, q_block, mlstm_chunk)
        x, a = _ffn(lp, cfg, x, moe_impl)
        aux = aux + a
    return x, aux


def _embed_inputs(params, cfg: ArchConfig, batch) -> Tuple[jax.Array, int]:
    """Token (+ frontend) embedding -> [B, S_total, D] bf16.

    VLM: frontend embeddings are prepended; returns the text offset.
    """
    tokens = batch["tokens"]
    h = params["embed"][tokens].astype(COMPUTE_DTYPE)
    offset = 0
    if cfg.frontend == "vit" and "patches" in batch:
        pe = (batch["patches"].astype(COMPUTE_DTYPE)
              @ params["frontend_proj"].astype(COMPUTE_DTYPE))
        h = jnp.concatenate([pe, h], axis=1)
        offset = pe.shape[1]
    return h, offset


def _encode(params, cfg: ArchConfig, frames, q_block: int,
            unroll: bool = False):
    """Audio/enc-dec encoder over precomputed frame embeddings."""
    h = (frames.astype(COMPUTE_DTYPE)
         @ params["frontend_proj"].astype(COMPUTE_DTYPE))
    B, S, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(x, lp):
        hn = rms_norm(x, lp["ln1"], cfg.norm_eps)
        out = A.attention_block(
            lp["mixer"], hn, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim_, rope_theta=cfg.rope_theta,
            window=jnp.int32(0), qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
            positions=pos, causal=False, q_block=q_block)
        x = x + out
        hn = rms_norm(x, lp["ln2"], cfg.norm_eps)
        return x + mlp_block(lp["mlp"], hn, cfg.act), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, params["enc_layers"],
                        unroll=unroll)
    return rms_norm(h, params["enc_ln_f"], cfg.norm_eps)


def _decoder_stack(params, cfg: ArchConfig, x, enc_out, positions,
                   q_block: int, unroll: bool = False):
    """Enc-dec decoder: causal self-attn + cross-attn + MLP per layer."""

    def body(carry, xs):
        x = carry
        lp, cp = xs
        hn = rms_norm(x, lp["ln1"], cfg.norm_eps)
        x = x + A.attention_block(
            lp["mixer"], hn, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim_, rope_theta=cfg.rope_theta,
            window=jnp.int32(0), qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
            positions=positions, q_block=q_block)
        hn = rms_norm(x, cp["ln1"], cfg.norm_eps)
        kv = A.cross_kv(cp["mixer"], enc_out, cfg.n_kv_heads, cfg.head_dim_)
        x = x + A.attention_block(
            cp["mixer"], hn, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim_, rope_theta=0.0, window=jnp.int32(0),
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps, positions=positions,
            kv_override=kv, q_block=q_block)
        hn = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + mlp_block(lp["mlp"], hn, cfg.act)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x,
                        (params["layers"], params["cross_layers"]),
                        unroll=unroll)
    return x


def forward(params, batch, cfg: ArchConfig, *, moe_impl: str = "dense",
            q_block: int = 512, unroll: bool = False,
            mlstm_chunk: int = 0,
            remat_policy: str = "full") -> Tuple[jax.Array, jax.Array]:
    """Training/prefill forward -> (logits [B,S,Vp], aux_loss)."""
    if cfg.family == "encdec":
        enc_out = _encode(params, cfg, batch["frames"], q_block,
                          unroll=unroll)
        x = params["embed"][batch["tokens"]].astype(COMPUTE_DTYPE)
        B, S, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = _decoder_stack(params, cfg, x, enc_out, pos, q_block,
                           unroll=unroll)
        aux = jnp.float32(0.0)
    else:
        x, _ = _embed_inputs(params, cfg, batch)
        B, S, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.block_pattern:
            x, aux = _macro_stack(params, cfg, x, pos, moe_impl, q_block,
                                  unroll=unroll, mlstm_chunk=mlstm_chunk,
                                  remat_policy=remat_policy)
        else:
            windows = layer_windows(cfg)
            x, aux = _dense_stack(params["layers"], cfg, x, windows, pos,
                                  moe_impl, q_block, unroll=unroll,
                                  mlstm_chunk=mlstm_chunk,
                                  remat_policy=remat_policy)
    logits = lm_head(params, x, cfg)
    return logits, jnp.asarray(aux, jnp.float32)


def lm_head(params, x, cfg: ArchConfig) -> jax.Array:
    """Final norm + vocab projection — the one LM-head implementation,
    shared by forward, decode_step and the pipelined step.  Columns past
    ``cfg.vocab_size`` (the padding to ``vocab_padded``) are masked to
    -inf, so no sampler or argmax can emit a token outside the vocab."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params["head"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
        if cfg.vocab_padded > cfg.vocab_size:
            real = jnp.arange(logits.shape[-1]) < cfg.vocab_size
            logits = jnp.where(real, logits, -jnp.inf)
        return logits


def greedy(logits) -> jax.Array:
    """Greedy sampling: the argmax token id (int32) over the last axis."""
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def token_ce_loss(logits, tokens, aux=0.0) -> jax.Array:
    """Next-token CE + z-loss (+ MoE aux) from full-sequence logits.

    The single source of the training objective's tail — shared by the
    plain train step and the pipelined step (repro.dist.pipeline), so
    the two can never drift apart.
    """
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    zloss = 1e-4 * (z ** 2)
    return nll.mean() + zloss.mean() + \
        MOE_AUX_WEIGHT * jnp.asarray(aux, jnp.float32)


def loss_fn(params, batch, cfg: ArchConfig, *, moe_impl: str = "dense",
            q_block: int = 512, unroll: bool = False,
            mlstm_chunk: int = 0, remat_policy: str = "full") -> jax.Array:
    """Next-token CE (+ z-loss + MoE aux)."""
    logits, aux = forward(params, batch, cfg, moe_impl=moe_impl,
                          q_block=q_block, unroll=unroll,
                          mlstm_chunk=mlstm_chunk,
                          remat_policy=remat_policy)
    if cfg.family == "encdec" or cfg.family == "vlm":
        # frontends are stubs; vlm logits include patch positions — slice
        if cfg.family == "vlm" and cfg.frontend_seq:
            logits = logits[:, batch["patches"].shape[1]:]
    return token_ce_loss(logits, batch["tokens"], aux)


# ------------------------------------------------------------- decoding
def init_decode_state(cfg: ArchConfig, batch: int, cache_len: int,
                      kv_dtype: str = "bf16") -> Params:
    """Allocate the decode cache/state tree for a batch.

    ``kv_dtype='int8'`` allocates the quantized cache (+ scale planes) —
    the serving analogue of the paper's action-bits quantization.
    """
    hd, KV = cfg.head_dim_, cfg.n_kv_heads
    # int8 applies to the dense-family KV cache only; recurrent states and
    # enc-dec cross caches keep bf16 (requests fall back silently)
    use_int8 = (kv_dtype == "int8" and not cfg.block_pattern
                and cfg.family != "encdec")
    kv_dt = jnp.int8 if use_int8 else COMPUTE_DTYPE

    def kv_cache(n, length):
        shape = (n, batch, length, KV, hd)
        return (jnp.zeros(shape, kv_dt), jnp.zeros(shape, kv_dt))

    def kv_scales(n, length):
        shape = (n, batch, length, KV, 1)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))

    state: Params = {"pos": jnp.int32(0)}
    if use_int8:
        state["kv"] = kv_cache(cfg.n_layers, cache_len)
        state["kv_scales"] = kv_scales(cfg.n_layers, cache_len)
        return state
    if cfg.family == "encdec":
        state["kv"] = kv_cache(cfg.n_layers, cache_len)
        # cross K/V precomputed from the encoder output at prefill time;
        # encoder length is the frontend frame budget
        enc_len = cfg.frontend_seq or cache_len
        shape = (cfg.n_layers, batch, enc_len, KV, hd)
        state["cross"] = (jnp.zeros(shape, kv_dt), jnp.zeros(shape, kv_dt))
        return state
    if not cfg.block_pattern:
        state["kv"] = kv_cache(cfg.n_layers, cache_len)
        return state
    pat, n_macro, n_tail = macro_pattern(cfg)
    # windowed attn layers cache only the window (the long_500k enabler)
    attn_len = min(cache_len,
                   cfg.local_window) if cfg.local_window else cache_len
    for i, kind in enumerate(pat):
        if kind == "attn":
            state[f"m{i}_kv"] = kv_cache(n_macro, attn_len)
        elif kind == "rglru":
            state[f"m{i}_rglru"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_macro, *x.shape)),
                R.rglru_init_state(batch, cfg.d_model))
        elif kind == "mlstm":
            state[f"m{i}_mlstm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_macro, *x.shape)),
                R.mlstm_init_state(batch, cfg.n_heads,
                                   cfg.d_model // cfg.n_heads))
        elif kind == "slstm":
            state[f"m{i}_slstm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_macro, *x.shape)),
                R.slstm_init_state(batch, cfg.n_heads,
                                   cfg.d_model // cfg.n_heads))
    for i in range(n_tail):
        kind = pat[i]
        if kind == "attn":
            state[f"tail{i}_kv"] = kv_cache(1, attn_len)
        elif kind == "rglru":
            state[f"tail{i}_rglru"] = R.rglru_init_state(batch, cfg.d_model)
        elif kind == "mlstm":
            state[f"tail{i}_mlstm"] = R.mlstm_init_state(
                batch, cfg.n_heads, cfg.d_model // cfg.n_heads)
        elif kind == "slstm":
            state[f"tail{i}_slstm"] = R.slstm_init_state(
                batch, cfg.n_heads, cfg.d_model // cfg.n_heads)
    return state


def init_paged_kv(cfg: ArchConfig, n_pages: int, page_size: int,
                  kv_dtype: str = "bf16") -> PagedKV:
    """Allocate the physical page pool for the paged KV cache.

    Returns a stacked :class:`~repro.nn.attn_backend.PagedKV` whose
    ``k``/``v`` pools are ``[n_layers, n_pages, page, KV * hd]`` (view
    fields ``None``): lane-dense, the layout the paged kernel reads, so
    every layer writes and reads it in place by layer index.  Unlike
    the dense ``[B, cache_len]`` cache,
    memory scales with the *pool*, not slots x max length — a block
    table per slot maps logical positions to pages, so short requests
    pin only the pages they reserve and freed pages recycle to the next
    admission.  Dense-family stacks only (hybrid/enc-dec decode keeps
    the dense cache).

    ``kv_dtype='int8'`` quantizes the pool (the paged analogue of the
    dense int8 cache): int8 value pools plus f32 per-token, per-head
    scale planes ``[n_layers, n_pages, page, KV]`` in
    ``k_scale``/``v_scale`` —
    the pool holds ~2x more tokens per byte at the
    ``quantize_kv_int8`` round-trip bound.
    """
    if cfg.block_pattern or cfg.family == "encdec":
        raise ValueError("paged KV cache supports dense attention "
                         f"stacks only (got family={cfg.family!r})")
    shape = (cfg.n_layers, n_pages, page_size,
             cfg.n_kv_heads * cfg.head_dim_)
    if kv_dtype == "int8":
        sshape = shape[:-1] + (cfg.n_kv_heads,)
        return PagedKV(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       k_scale=jnp.zeros(sshape, jnp.float32),
                       v_scale=jnp.zeros(sshape, jnp.float32))
    return PagedKV(k=jnp.zeros(shape, COMPUTE_DTYPE),
                   v=jnp.zeros(shape, COMPUTE_DTYPE))


def paged_decode_step(params, kv, block_tbl, pos, tokens, n_new,
                      cfg: ArchConfig, *, moe_impl: str = "dense",
                      unroll: bool = False, sample_greedy: bool = False,
                      attn_impl: str = "jnp", all_positions: bool = False,
                      ) -> Tuple[jax.Array, PagedKV]:
    """Chunked multi-token decode/prefill through the paged KV cache.

    ``tokens [B, C]`` carries up to ``C`` new tokens per slot
    (``n_new[b]`` valid, left-aligned), each slot at its own absolute
    offset ``pos[b]`` — this is what the dense ``decode_step`` cannot
    do: its position is one global scalar, so prompts must enter one
    token per launch.  Here a P-token prompt costs ``ceil(P/C)``
    launches and every slot advances independently.

    Returns logits (or greedy tokens) at each slot's *last valid*
    chunk position — mid-prompt predictions are computed but discarded
    by the caller, matching token-by-token seeding bit for bit.
    ``n_new[b] = 0`` marks an idle slot: its writes drop and its output
    row is garbage (finite), never read.

    ``kv`` is the pool-level :class:`~repro.nn.attn_backend.PagedKV`
    from ``init_paged_kv`` (bf16, or int8 + scale planes — the int8
    path quantizes on write and dequantizes inside the gathered
    attention, mirroring the dense ``decode_step`` int8 cache).  The
    stacked pool rides in the layer scan's carry beside the
    activations, and only the layer index, the layer's params and its
    window are scanned over: each layer scatters its chunk's K/V into
    ``pool[layer, page, off]`` and the attention backend reads that
    layer's pages in place, so no layer's pool is sliced out, restacked
    or relaid out.

    ``attn_impl`` picks the attention backend
    (``attn_backend.resolve``: ``'jnp'`` | ``'pallas'`` | ``'auto'``);
    it is resolved once here, outside the scan (backends agree to the
    tolerance stated in ``nn.attn_backend``).

    ``all_positions=True`` skips the last-valid-position narrowing and
    projects every chunk position through the head: logits (or greedy
    tokens) come back ``[B, C(, Vp)]`` — position ``j`` predicts the
    token after ``tokens[:, j]``.  This is the speculative-decoding
    verify primitive: ``rms_norm`` + the head einsum are per-position,
    so row ``n_new[b]-1`` is bit-identical to the narrowed output.
    """
    if not isinstance(kv, PagedKV):
        raise TypeError(
            "paged_decode_step expects the PagedKV from init_paged_kv; "
            "the legacy (k, v[, sk, sv]) tuple pool was removed after "
            f"its one-release deprecation window (got {type(kv)})")
    kv = kv.pool()  # stray view fields would confuse the layer scan
    impl = AB.resolve(attn_impl)
    B, C = tokens.shape
    N_pages, page = kv.n_pages, kv.page_size
    n_ps = block_tbl.shape[1]
    positions = pos[:, None] + jnp.arange(C)[None]  # [B, C] absolute
    valid = jnp.arange(C)[None] < n_new[:, None]
    lp = jnp.clip(positions // page, 0, n_ps - 1)
    page_ids = jnp.take_along_axis(block_tbl, lp, axis=1)
    page_ids = jnp.where(valid, page_ids, N_pages)  # N = dropped write
    page_off = positions % page
    x = params["embed"][tokens].astype(COMPUTE_DTYPE)
    windows = jnp.asarray(layer_windows(cfg))

    def body(carry, xs):
        x, pool = carry
        layer_p, w, layer = xs
        h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
        out, pool = A.paged_decode_attention_block(
            layer_p["mixer"], h,
            pool.with_view(block_tbl, positions, page_ids, page_off, layer),
            n_heads=cfg.q_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim_, rope_theta=cfg.rope_theta, window=w,
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps, impl=impl)
        x = x + out
        x, _ = _ffn(layer_p, cfg, x, moe_impl)
        return (x, pool.pool()), None

    (x, new_kv), _ = jax.lax.scan(
        body, (x, kv),
        (params["layers"], windows, jnp.arange(cfg.n_layers, dtype=jnp.int32)),
        unroll=unroll)
    if all_positions:
        logits = lm_head(params, x, cfg)  # [B, C, Vp]
        if sample_greedy:
            return greedy(logits), new_kv
        return logits, new_kv
    # select each slot's last valid position BEFORE the vocab
    # projection: the head is the dominant decode matmul and only one
    # chunk position per slot is kept (rms_norm + einsum are
    # per-position, so this is bit-identical to projecting all C)
    last = jnp.clip(n_new - 1, 0, C - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = lm_head(params, x, cfg)[:, 0]
    if sample_greedy:
        return greedy(logits), new_kv
    return logits, new_kv


def _decode_mixer(lp, cfg: ArchConfig, kind: str, x, window, cache, pos,
                  gqa_impl: str = "repeat", kv_scales=None):
    """One decode step through one mixer; returns (x, new_cache[, scales])."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "attn":
        ck, cv = cache
        out, ck, cv, new_scales = A.decode_attention_block(
            lp["mixer"], h, ck, cv, pos, n_heads=cfg.q_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_,
            rope_theta=cfg.rope_theta, window=window, qk_norm=cfg.qk_norm,
            norm_eps=cfg.norm_eps, gqa_impl=gqa_impl, kv_scales=kv_scales)
        if kv_scales is not None:
            return x + out, (ck, cv), new_scales
        return x + out, (ck, cv)
    if kind == "rglru":
        out, st = R.rglru_decode(lp["mixer"], h, cache)
        return x + out, st
    if kind == "mlstm":
        out, st = R.mlstm_decode(lp["mixer"], h, cache, cfg.n_heads)
        return x + out, st
    if kind == "slstm":
        out, st = R.slstm_decode(lp["mixer"], h, cache, cfg.n_heads)
        return x + out, st
    raise ValueError(kind)


def decode_step(params, state, tokens, cfg: ArchConfig, *,
                moe_impl: str = "dense", unroll: bool = False,
                gqa_impl: str = "repeat",
                sample_greedy: bool = False) -> Tuple[jax.Array, Params]:
    """One token for every sequence in the batch.  tokens [B, 1].

    ``sample_greedy=True`` returns ``(next_tokens [B] int32, state)``
    instead of ``(logits [B, Vp], state)`` — the argmax stays on device,
    so serving loops never sync a [B, Vp] logits plane to host just to
    pick a token (the device-resident batcher and ``ServeEngine.generate``
    both build on this).
    """
    pos = state["pos"]
    x = params["embed"][tokens].astype(COMPUTE_DTYPE)
    new_state: Params = {"pos": pos + 1}

    if cfg.family == "encdec":
        ck, cv = state["kv"]
        xk, xv = state["cross"]

        def body(x, xs):
            lp, cp, ck_l, cv_l, xk_l, xv_l = xs
            x, (ck_l, cv_l) = _decode_mixer(lp, cfg, "attn", x,
                                            jnp.int32(0), (ck_l, cv_l), pos)
            # cross-attention over the (static) encoder K/V
            h = rms_norm(x, cp["ln1"], cfg.norm_eps)
            q = (h @ cp["mixer"]["wq"].astype(h.dtype)).reshape(
                x.shape[0], 1, cfg.n_heads, cfg.head_dim_)
            kf = A._repeat_kv(xk_l.astype(h.dtype), cfg.n_heads)
            vf = A._repeat_kv(xv_l.astype(h.dtype), cfg.n_heads)
            s = jnp.einsum("bqhd,bshd->bhqs", q, kf) / np.sqrt(cfg.head_dim_)
            probs = jax.nn.softmax(s.astype(jnp.float32), -1).astype(h.dtype)
            o = jnp.einsum("bhqs,bshd->bqhd", probs, vf).reshape(
                x.shape[0], 1, cfg.n_heads * cfg.head_dim_)
            x = x + o @ cp["mixer"]["wo"].astype(h.dtype)
            x, _ = _ffn(lp, cfg, x, moe_impl)
            return x, (ck_l, cv_l)

        x, (ck, cv) = jax.lax.scan(
            body, x, (params["layers"], params["cross_layers"], ck, cv,
                      xk, xv), unroll=unroll)
        new_state["kv"] = (ck, cv)
        new_state["cross"] = state["cross"]
    elif not cfg.block_pattern:
        windows = jnp.asarray(layer_windows(cfg))
        ck, cv = state["kv"]
        int8 = "kv_scales" in state

        if int8:
            sk, sv = state["kv_scales"]

            def body8(x, xs):
                lp, ck_l, cv_l, sk_l, sv_l, w = xs
                x, (ck_l, cv_l), (sk_l, sv_l) = _decode_mixer(
                    lp, cfg, "attn", x, w, (ck_l, cv_l), pos,
                    gqa_impl=gqa_impl, kv_scales=(sk_l, sv_l))
                x, _ = _ffn(lp, cfg, x, moe_impl)
                return x, (ck_l, cv_l, sk_l, sv_l)

            x, (ck, cv, sk, sv) = jax.lax.scan(
                body8, x, (params["layers"], ck, cv, sk, sv, windows),
                unroll=unroll)
            new_state["kv"] = (ck, cv)
            new_state["kv_scales"] = (sk, sv)
        else:
            def body(x, xs):
                lp, ck_l, cv_l, w = xs
                x, (ck_l, cv_l) = _decode_mixer(lp, cfg, "attn", x, w,
                                                (ck_l, cv_l), pos,
                                                gqa_impl=gqa_impl)
                x, _ = _ffn(lp, cfg, x, moe_impl)
                return x, (ck_l, cv_l)

            x, (ck, cv) = jax.lax.scan(body, x,
                                       (params["layers"], ck, cv, windows),
                                       unroll=unroll)
            new_state["kv"] = (ck, cv)
    else:
        pat, n_macro, n_tail = macro_pattern(cfg)

        def body(x, xs):
            outs = {}
            for i, kind in enumerate(pat):
                lp = xs[f"m{i}_{kind}"]
                cache = xs[f"m{i}_cache"]
                if kind == "attn":
                    cache = (cache[0], cache[1])
                x, nc = _decode_mixer(lp, cfg, kind, x,
                                      jnp.int32(cfg.local_window), cache, pos)
                outs[f"m{i}_cache"] = nc
                x, _ = _ffn(lp, cfg, x, moe_impl)
            return x, outs

        xs = dict(params["macros"])
        for i, kind in enumerate(pat):
            key = f"m{i}_kv" if kind == "attn" else f"m{i}_{kind}"
            xs[f"m{i}_cache"] = state[key]
        x, outs = jax.lax.scan(body, x, xs, unroll=unroll)
        for i, kind in enumerate(pat):
            key = f"m{i}_kv" if kind == "attn" else f"m{i}_{kind}"
            new_state[key] = outs[f"m{i}_cache"]
        for i in range(n_tail):
            kind = pat[i]
            key = f"tail{i}_kv" if kind == "attn" else f"tail{i}_{kind}"
            cache = state[key]
            x, nc = _decode_mixer(params["tail"][i], cfg, kind, x,
                                  jnp.int32(cfg.local_window), cache, pos)
            new_state[key] = nc
            x, _ = _ffn(params["tail"][i], cfg, x, moe_impl)

    logits = lm_head(params, x, cfg)[:, 0]
    if sample_greedy:
        return greedy(logits), new_state
    return logits, new_state
