"""shard_map MoE dispatch: manual all_to_all expert parallelism.

The GShard one-hot einsum dispatch (moe.py) costs O(T * S_g * cf * M) FLOPs
and GSPMD replicates tokens when given the algebraically-equivalent
scatter/gather formulation (EXPERIMENTS.md H5). The standard production fix
is to take dispatch out of GSPMD's hands: inside shard_map each device

  1. routes its local tokens (top-k + capacity, identical to moe.py),
  2. scatters them into an (n_shards, E_local, C_local, M) send buffer,
  3. ``jax.lax.all_to_all`` over the model axis delivers every expert's
     tokens to its owner shard,
  4. local expert FFN (analog-mapped),
  5. all_to_all back + local gather/combine.

Zero dispatch FLOPs, no replication: per-device traffic is exactly the
routed activations (T_local * cf * k * M), the information-theoretic
minimum. Falls back to the einsum path when no mesh is active (CPU tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.analog import AnalogCtx
from repro.models import moe as moe_lib
from repro.models.common import ModelConfig

Array = jax.Array


def moe_apply_shardmap(
    params: dict, x: Array, ctx: AnalogCtx, cfg: ModelConfig
) -> Array:
    """x: (B, S, M) batch-sharded over the data axes; experts over model."""
    # the mesh entered with ``jax.set_mesh`` (empty outside one)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return moe_lib.moe_apply(params, x, ctx, cfg)
    n_model = mesh.shape["model"]
    e = cfg.n_experts
    if e % n_model != 0:
        return moe_lib.moe_apply(params, x, ctx, cfg)

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b, s, m = x.shape
    k = cfg.top_k
    e_loc = e // n_model
    # static per-bank ADC bitwidth (mixed-precision programs): resolved from
    # the shape-encoded buffer HERE -- shapes are static, so the int can be
    # closed over by the shard_map body (unlike the param tracer itself).
    # Per-MVM read-noise resampling is an einsum-dispatch feature; this path
    # always executes the program's frozen (bit-exact) read draw.
    from repro.core import engine as engine_lib

    bank_b_adc = engine_lib.bits_of(params.get("b_adc_buf"))

    def local_moe(x_loc, router_w, w1, w3, w2, r_adc, clip_buf, scales, gain_s):
        # x_loc: (b_loc, s, m); expert shards w*: (e_loc, ., .)
        # rebuild the analog ctx INSIDE the shard_map body (closing over
        # outer tracers is illegal); decorrelate per-shard noise keys
        key = None
        if ctx.key is not None:
            key = jax.random.fold_in(ctx.key, jax.lax.axis_index("model"))
        ctx_local = AnalogCtx(cfg=ctx.cfg, gain_s=gain_s, key=key)
        bl = x_loc.shape[0]
        toks = x_loc.reshape(bl * s, m)
        t_loc = toks.shape[0]
        cap = max(1, int(t_loc * k * cfg.capacity_factor / e))

        logits = jnp.einsum(
            "tm,me->te", toks.astype(jnp.float32), router_w
        )
        gates = jax.nn.softmax(logits, axis=-1)
        idxs, poss, keeps, gvals = moe_lib._topk_routing(
            gates[None], k, cap
        )  # add a dummy group dim
        # send buffer: (E, C, M) built locally -- scatter is DEVICE-LOCAL
        send = jnp.zeros((e, cap, m), x_loc.dtype)
        for idx, pos in zip(idxs, poss):
            send = send.at[idx[0], pos[0]].set(toks, mode="drop")
        # exchange: (n_model, e_loc, C, M) -> every shard owns its experts'
        # tokens from all shards
        send = send.reshape(n_model, e_loc, cap, m)
        recv = jax.lax.all_to_all(
            send, "model", split_axis=0, concat_axis=0, tiled=False
        )  # (n_model, e_loc, C, M) with leading dim now = source shard
        recv = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_model * cap, m)

        # local expert FFN (analog-mapped, same math as moe._expert_ffn)
        fake = {
            "w1": w1, "w3": w3, "w2": w2,
            "r_adc": r_adc, "w_clip_buf": clip_buf,
            "out_scale_buf": scales,  # per-(family, local expert) GDC
        }
        ye = moe_lib._expert_ffn(
            fake, recv[:, None], ctx_local, x_loc.dtype, b_adc=bank_b_adc
        )[:, 0]

        # return to senders
        back = ye.reshape(e_loc, n_model, cap, m).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(
            back, "model", split_axis=0, concat_axis=0, tiled=False
        )  # (n_model, e_loc, C, M) -> this shard's tokens, expert-major
        back = back.reshape(e, cap, m)

        y = jnp.zeros_like(toks)
        for idx, pos, keep, gv in zip(idxs, poss, keeps, gvals):
            picked = back[idx[0], jnp.minimum(pos[0], cap - 1)]
            y = y + jnp.where(
                keep[0][:, None], picked * gv[0][:, None].astype(y.dtype), 0
            )
        return y.reshape(bl, s, m)

    from jax.experimental.shard_map import shard_map

    b_spec = P(data_axes if len(data_axes) != 1 else data_axes[0], None, None)
    e_spec3 = P("model", None, None)
    scales = params.get("out_scale_buf")
    if scales is None:
        scales = jnp.ones((3, e), jnp.float32)
    fn = shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(
            b_spec,  # x
            P(None, None),  # router (replicated)
            e_spec3, e_spec3, e_spec3,  # expert banks
            P(None),  # r_adc
            P(None, None),  # clip buf
            P(None, "model"),  # per-(family, expert) GDC scales
            P(),  # gain_s
        ),
        out_specs=b_spec,
        check_rep=False,
    )
    y = fn(
        x,
        params["router"]["w"],
        params["w1"], params["w3"], params["w2"],
        params["r_adc"], params["w_clip_buf"], scales, ctx.gain_s,
    )
    if "shared" in params:
        # The always-on shared expert is token-pointwise (no dispatch), so
        # it runs outside the all_to_all exchange on the batch-sharded
        # tokens; the einsum path adds the identical term.
        y = y + moe_lib.shared_expert_apply(params, x, ctx)
    return y
