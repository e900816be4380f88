"""Mixture-of-Experts FFN with GShard-style grouped einsum dispatch.

Token dispatch uses capacity-bounded one-hot einsums over *groups* of tokens
(the Mesh-TF/GShard formulation): tokens are reshaped to (G, S_g, M) with G
sharded over the data axes and experts sharded over the model axis, so the
dispatch einsum lowers to an all-to-all under GSPMD -- the canonical
expert-parallel pattern. Group size bounds the dispatch tensor to
(G, S_g, E, C) with C = S_g * top_k * capacity_factor / E.

Expert FFNs are stationary-weight matmuls and therefore analog-CiM-mapped:
each expert's (w1, w3, w2) go through a vmapped AnalogLinear with a per-layer
shared r_ADC (the paper's per-layer fixed-gain constraint; experts within a
layer share the physical ADC configuration). The *router* stays digital: it
is exactly the narrow, noise-sensitive bottleneck the paper removes from its
models (Sec. 4.1 "small layers are bottlenecks") -- see DESIGN.md.

Dropless dispatch (``cfg.moe_dispatch == "dropless"``, the inference and
serving path of LFM2): the layer holds a share of the experts
(``cfg.moe_held``), routes every token over all ``cfg.n_experts`` with
LFM2's rule (:func:`route_sigmoid_bias`), and runs every held expert's
programmed bank over every row, weighting each row by its routing weight
for that expert (0 where the token did not choose it). No token is dropped
and no row depends on another, so a batch's rows are independent. The
capacity paths stay for training.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import engine as engine_lib
from repro.core.analog import AnalogCtx, analog_matmul
from repro.core.engine import PCM_PROGRAMMED
from repro.models.common import ModelConfig, shard

Array = jax.Array


#: the dropless layer's counts, in the order of its stats vector: (token,
#: held expert) pairs routed, rows the expert MVMs ran (padding included),
#: the most tokens one held expert was routed, and the tokens the layer
#: took. A token is a real row: not a pad position of a right-padded
#: prefill, nor an idle decode slot (the ``valid`` mask the layer is given)
STAT_NAMES = (
    "moe.rows_held", "moe.rows_computed", "moe.rows_max_expert", "moe.tokens"
)


def held_experts(cfg: ModelConfig) -> tuple:
    """The ids of the experts this layer holds (all when none are named)."""
    return tuple(cfg.moe_held) or tuple(range(cfg.n_experts))


def moe_init(key: Array, cfg: ModelConfig) -> dict:
    e, m, h = len(held_experts(cfg)), cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    k1, k2, k3, kr, ks = jax.random.split(key, 5)
    s_in, s_h = m**-0.5, h**-0.5
    params = {
        "router": {
            "w": jax.random.normal(kr, (m, cfg.n_experts), jnp.float32) * s_in
        },
        "w1": jax.random.normal(k1, (e, m, h), jnp.float32) * s_in,
        "w3": jax.random.normal(k3, (e, m, h), jnp.float32) * s_in,
        "w2": jax.random.normal(k2, (e, h, m), jnp.float32) * s_h,
        "r_adc": jnp.ones((3,), jnp.float32),  # per matmul family (w1,w3,w2)
        "w_clip_buf": jnp.tile(jnp.array([-1.0, 1.0], jnp.float32), (3, 1)),
    }
    if cfg.moe_dispatch == "dropless":
        params["expert_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.shared_expert:
        from repro.core.analog import linear_init

        ke1, ke2, ke3 = jax.random.split(ks, 3)
        params["shared"] = {
            "w1": linear_init(ke1, m, h),
            "w3": linear_init(ke3, m, h),
            "w2": linear_init(ke2, h, m),
        }
    return params


def _expert_ffn(
    params: dict, x: Array, ctx: AnalogCtx, dtype, b_adc=None
) -> Array:
    """x: (E, G, C, M) -> (E, G, C, M); SwiGLU per expert, analog-mapped.

    Each MVM runs in the bank's own dtype (f32 effective weights in a
    programmed chip), as every programmed linear layer does.

    ``out_scale_buf`` (3, E) carries per-(family, expert) GDC scalars when
    the expert bank was programmed by ``engine.compile_program``; otherwise
    the scales are 1 (training / per-call modes ignore them). ``b_adc`` is
    the bank's per-layer ADC bitwidth (mixed-precision programs); when None
    it is recovered from the bank's shape-encoded ``b_adc_buf`` (the
    shard_map dispatch resolves it outside its body and passes it in, since
    closing over param tracers inside shard_map is illegal). A programmed
    bank with ``read_buf`` + RNG resamples per-MVM read noise for the whole
    bank before the expert vmap.
    """
    scales = params.get("out_scale_buf")
    if scales is None:
        scales = jnp.ones((3, params["w1"].shape[0]), jnp.float32)
    if b_adc is None:
        b_adc = engine_lib.bits_of(params.get("b_adc_buf"))

    bank = {f: params[f] for f in ("w1", "w3", "w2")}
    read_buf = params.get("read_buf")
    if (
        read_buf is not None
        and ctx.cfg.mode == PCM_PROGRAMMED
        and ctx.cfg.resample_read_noise
        and ctx.key is not None
    ):
        for fam in bank:
            bank[fam] = engine_lib.resample_read(
                ctx.next_key(), read_buf[fam]
            ).astype(params[fam].dtype)

    def one_expert(w1, w3, w2, clip1, clip3, clip2, s, xe):
        h1 = analog_matmul(
            xe,
            w1,
            r_adc=params["r_adc"][0],
            w_min=clip1[0],
            w_max=clip1[1],
            ctx=ctx,
            out_scale=s[0],
            b_adc=b_adc,
        )
        h3 = analog_matmul(
            xe,
            w3,
            r_adc=params["r_adc"][1],
            w_min=clip3[0],
            w_max=clip3[1],
            ctx=ctx,
            out_scale=s[1],
            b_adc=b_adc,
        )
        h = jax.nn.silu(h1) * h3
        return analog_matmul(
            h,
            w2,
            r_adc=params["r_adc"][2],
            w_min=clip2[0],
            w_max=clip2[1],
            ctx=ctx,
            out_scale=s[2],
            b_adc=b_adc,
        )

    clip = params["w_clip_buf"]
    return jax.vmap(one_expert, in_axes=(0, 0, 0, None, None, None, 1, 0))(
        bank["w1"], bank["w3"], bank["w2"],
        clip[0], clip[1], clip[2], scales, x
    )


def shared_expert_apply(params: dict, x: Array, ctx: AnalogCtx) -> Array:
    """The always-on shared expert (llama4-style): a SwiGLU of analog
    linears applied to every token, added to the routed-expert output.
    Token-pointwise, so any (..., M) layout gives identical results --
    both dispatch paths (einsum and shard_map) call this on their own
    token layout."""
    from repro.core.analog import linear_apply

    sh = params["shared"]
    h = jax.nn.silu(linear_apply(sh["w1"], x, ctx)) * linear_apply(
        sh["w3"], x, ctx
    )
    return linear_apply(sh["w2"], h, ctx)


def _topk_routing(gates: Array, k: int, cap: int):
    """Iterative top-k with per-expert capacity. gates: (G, Sg, E).

    Returns per-choice lists of: expert index (G,Sg), buffer slot (G,Sg),
    keep mask (G,Sg), gate value (G,Sg). FLOP cost is O(T*E) -- no one-hot
    outer products.
    """
    g, sg, e = gates.shape
    idxs, poss, keeps, gvals = [], [], [], []
    gates_left = gates
    fills = jnp.zeros((g, e), jnp.int32)
    for _ in range(k):
        idx = jnp.argmax(gates_left, axis=-1)  # (g, sg)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)
        pos_e = jnp.cumsum(onehot, axis=1) - onehot + fills[:, None, :]
        pos = jnp.take_along_axis(pos_e, idx[..., None], axis=-1)[..., 0]
        keep = pos < cap
        gv = jnp.take_along_axis(gates, idx[..., None], axis=-1)[..., 0]
        idxs.append(idx)
        poss.append(pos)  # unclamped: OOB slots = dropped tokens
        keeps.append(keep)
        gvals.append(gv)
        fills = fills + onehot.sum(axis=1)
        gates_left = gates_left * (1.0 - onehot.astype(gates.dtype))
    return idxs, poss, keeps, gvals


def moe_apply(params: dict, x: Array, ctx: AnalogCtx, cfg: ModelConfig) -> Array:
    """x: (B, S, M) -> (B, S, M)."""
    b, s, m = x.shape
    e, k = cfg.n_experts, cfg.top_k
    dtype = x.dtype
    tokens = b * s
    g = min(cfg.moe_groups, tokens)
    while tokens % g:
        g -= 1
    sg = tokens // g
    cap = max(1, int(sg * k * cfg.capacity_factor / e))

    xt = x.reshape(g, sg, m)
    xt = shard(xt, "moe_groups", None, None)

    # --- router (digital, fp32) ---
    logits = jnp.einsum(
        "gsm,me->gse", xt.astype(jnp.float32), params["router"]["w"]
    )
    gates = jax.nn.softmax(logits, axis=-1)

    # top-k gating with per-expert capacity (GShard): iteratively take the
    # best expert, mask, repeat. Positions within each expert buffer come
    # from a cumsum over the token axis.
    idxs, poss, keeps, gvals = _topk_routing(gates, k, cap)
    if cfg.moe_dispatch != "scatter":
        dispatch = jnp.zeros((g, sg, e, cap), dtype)
        combine = jnp.zeros((g, sg, e, cap), jnp.float32)
        for idx, pos, keep, gv in zip(idxs, poss, keeps, gvals):
            e_oh = jax.nn.one_hot(idx, e, dtype=jnp.float32) * keep[..., None]
            pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)
            oh = e_oh[..., :, None] * pos_oh[..., None, :]
            dispatch = dispatch + oh.astype(dtype)
            combine = combine + oh * gv[..., None, None]

    if cfg.moe_dispatch == "scatter":
        # Index-based dispatch: the one-hot einsums cost O(T*E*C*M) FLOPs --
        # at 128 experts that EXCEEDS the expert FFNs themselves (E*C ~
        # 1.7x of 3*d_ff*top_k on llama4-maverick). Algebraically the same
        # contraction factorises into a scatter (dispatch) and a gather
        # (combine) with zero FLOPs.
        # per-(token, k): target expert idx_k (g, sg) and slot pos_k (g, sg)
        xe = jnp.zeros((e, g, cap, m), dtype)
        gi = jnp.arange(g)[:, None]
        for idx_k, pos_k in zip(idxs, poss):
            # out-of-capacity positions land out of bounds -> mode="drop"
            xe = xe.at[idx_k, gi, pos_k].set(xt, mode="drop")
        xe = shard(xe, "experts", None, None, None)
        ye = _expert_ffn(params, xe, ctx, dtype)
        y = jnp.zeros_like(xt)
        for idx_k, pos_k, keep_k, gv in zip(idxs, poss, keeps, gvals):
            picked = ye[idx_k, gi, jnp.minimum(pos_k, cap - 1)]  # gather
            y = y + jnp.where(
                keep_k[..., None], picked * gv[..., None].astype(dtype), 0
            )
        y = shard(y, "moe_groups", None, None)
    else:
        # --- dispatch: (G,Sg,E,C) x (G,Sg,M) -> (E,G,C,M): all-to-all under
        # SPMD (the GShard einsum formulation)
        xe = jnp.einsum("gsec,gsm->egcm", dispatch, xt)
        xe = shard(xe, "experts", None, None, None)
        ye = _expert_ffn(params, xe, ctx, dtype)
        # --- combine back to token layout ---
        y = jnp.einsum("gsec,egcm->gsm", combine.astype(dtype), ye)
        y = shard(y, "moe_groups", None, None)

    if "shared" in params:
        y = y + shared_expert_apply(params, xt, ctx)

    return y.reshape(b, s, m)


def route_sigmoid_bias(logits: Array, bias: Array, k: int):
    """LFM2's routing of f32 router logits (T, E) over every expert.

    Scores are ``sigmoid(logits)``; the ``k`` experts with the highest
    ``score + bias`` are chosen, ties to the lowest expert index (``argmax``
    takes the first maximum); their weights are the chosen scores
    normalised to sum 1. Returns (ids (T, k) int32, weights (T, k) f32).
    """
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    sel = s + bias.astype(jnp.float32)
    ids = []
    for _ in range(k):
        i = jnp.argmax(sel, axis=-1)
        ids.append(i)
        sel = jnp.where(
            jnp.arange(sel.shape[-1]) == i[:, None], -jnp.inf, sel
        )
    ids = jnp.stack(ids, axis=-1).astype(jnp.int32)
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / jnp.sum(w, axis=-1, keepdims=True)


def moe_apply_dropless(
    params: dict,
    x: Array,
    ctx: AnalogCtx,
    cfg: ModelConfig,
    valid: Optional[Array] = None,
) -> tuple[Array, Array]:
    """x: (B, S, M) -> (y, stats): the held experts' part of the layer.

    The router (digital, f32 at ``Precision.HIGHEST``) scores all
    ``cfg.n_experts``; every held expert's programmed bank runs over every
    row; a row's output is the sum, over held experts in id order, of its
    routing weight for the expert (0 where not chosen) times the expert's
    output, in f32. What the experts held elsewhere would add is left out.
    ``stats`` is the int32 vector of :data:`STAT_NAMES`; ``valid`` (B, S)
    marks the real rows it counts as tokens (all rows when None). The
    outputs do not depend on it.
    """
    b, s, m = x.shape
    t = b * s
    held = held_experts(cfg)
    xt = x.reshape(t, m)
    real = jnp.ones((t,), bool) if valid is None else valid.reshape(t)
    with jax.named_scope("router"):
        logits = jnp.einsum(
            "tm,me->te", xt.astype(jnp.float32), params["router"]["w"],
            precision=jax.lax.Precision.HIGHEST,
        )
        ids, wts = route_sigmoid_bias(logits, params["expert_bias"], cfg.top_k)
    with jax.named_scope("dispatch"):
        hit = ids[:, :, None] == jnp.asarray(held, jnp.int32)  # (T, k, H)
        gate = jnp.sum(jnp.where(hit, wts[..., None], 0.0), axis=1)  # (T, H)
        per_expert = jnp.sum(  # (H,) real rows routed to each
            hit & real[:, None, None], axis=(0, 1), dtype=jnp.int32
        )
        xe = jnp.broadcast_to(xt, (len(held), 1, t, m))
    with jax.named_scope("experts"):
        ye = _expert_ffn(params, xe, ctx, x.dtype)[:, 0]  # (H, T, M)
    with jax.named_scope("combine"):
        y = gate[:, :1] * ye[0].astype(jnp.float32)
        for j in range(1, len(held)):
            y = y + gate[:, j : j + 1] * ye[j].astype(jnp.float32)
    stats = jnp.stack([
        jnp.sum(per_expert), jnp.int32(len(held) * t), jnp.max(per_expert),
        jnp.sum(real, dtype=jnp.int32),
    ]).astype(jnp.int32)
    return y.astype(x.dtype).reshape(b, s, m), stats


def aux_load_balance_loss(logits: Array, dispatch: Array) -> Array:
    """Switch-style auxiliary loss (kept for training completeness)."""
    gates = jax.nn.softmax(logits, axis=-1)
    density = dispatch.sum(axis=-1).mean(axis=(0, 1))  # per-expert usage
    density_proxy = gates.mean(axis=(0, 1))
    e = gates.shape[-1]
    return e * jnp.sum(density * density_proxy)
