"""Top-k routed Mixture-of-Experts (granite-moe 32e/top-8, olmoe 64e/top-8).

TPU-native dispatch: tokens are scattered into fixed-capacity per-expert
buffers — position within the buffer comes from a cumulative count over the
routing one-hot (no sort, no dynamic shapes). The expert FFN is then ONE
batched matmul that shards over the `expert` logical axis (EP on the
`model` mesh axis). Overflowing tokens are dropped (GShard-style);
capacity_factor controls the drop rate.

Two dispatch modes (cfg.moe_dispatch_chunks):
  0   global buffers (E, cap, D) — the straightforward formulation; XLA
      must reshard the token slab from data-sharded rows into the
      expert-sharded buffer => heavy dispatch collectives (the measured
      §Perf baseline).
  C>1 chunk-local buffers (C, E, cap_c, D) with the chunk axis sharded
      over `data`: every data shard scatters ONLY its own tokens into its
      own slab and combines locally — zero cross-device traffic in
      dispatch/combine; capacity is enforced per chunk (slightly stricter
      than global capacity, which also improves balance). §Perf #B.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init
from repro.models.sharding_ctx import constrain

Array = jax.Array


def moe_init(key, cfg: ModelConfig) -> dict:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (d, e)),
        "w_gate": jax.vmap(lambda k: dense_init(k, (d, f)))(
            jax.random.split(k2, e)),
        "w_up": jax.vmap(lambda k: dense_init(k, (d, f)))(
            jax.random.split(k3, e)),
        "w_down": jax.vmap(lambda k: dense_init(k, (f, d)))(
            jax.random.split(k4, e)),
    }


def moe_spec(cfg: ModelConfig) -> dict:
    return {
        "router": ("embed", None),
        "w_gate": ("expert", "embed", None),
        "w_up": ("expert", "embed", None),
        "w_down": ("expert", None, "embed"),
    }


def moe(params: dict, x: Array, cfg: ModelConfig) -> Array:
    """x: (B, S, D) -> (B, S, D); aux loss discarded (serve path)."""
    out, _ = moe_with_aux(params, x, cfg)
    return out


def moe_with_aux(params: dict, x: Array, cfg: ModelConfig
                 ) -> tuple[Array, Array]:
    if cfg.moe_dispatch_chunks == -1:
        from repro.models.sharding_ctx import current_mesh
        mesh = current_mesh()
        if mesh is not None:
            return _moe_shard_map(params, x, cfg, mesh)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.experts_per_token
    chunks = cfg.moe_dispatch_chunks
    if chunks <= 1 or t % chunks != 0:
        chunks = 1
    tc = t // chunks
    cap = int(cfg.capacity_factor * tc * k / e)
    cap = max(8, -(-cap // 8) * 8)                        # round up, min 8
    dt = x.dtype

    xt = x.reshape(chunks, tc, d)
    xt = constrain(xt, ("moe_chunk", None, "act_embed"))
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)               # (C, Tc, E)
    top_p, top_e = jax.lax.top_k(probs, k)                # (C, Tc, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e, averaged chunks
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.float32)  # (C, Tc, k, E)
    f_e = jnp.mean(jnp.sum(onehot, axis=2), axis=1)       # (C, E)
    p_e = jnp.mean(probs, axis=1)
    aux = e * jnp.mean(jnp.sum(f_e * p_e, axis=-1))

    # position within each (chunk, expert) buffer: exclusive cumcount over
    # the flattened (Tc*k, E) one-hot, independent per chunk
    flat_oh = onehot.reshape(chunks, tc * k, e)
    pos_in_e = jnp.cumsum(flat_oh, axis=1) - flat_oh
    pos = jnp.sum(pos_in_e * flat_oh, axis=-1).astype(jnp.int32)  # (C, Tc*k)
    expert = top_e.reshape(chunks, tc * k)
    keep = pos < cap
    row = jnp.where(keep, expert, e)                      # drop route -> E

    # scatter tokens into (C, E+1, cap, D) buffers — chunk-local. Every
    # scatter operand (target, indices, updates) is constrained to the SAME
    # chunk sharding BEFORE the scatter so XLA partitions it as an
    # embarrassingly-parallel per-chunk op (without this it reconciles
    # mismatched operands with whole-buffer all-reduces — measured 5-8x
    # WORSE than the global-dispatch baseline; see §Perf #B1/#B2).
    cidx = jnp.broadcast_to(
        jnp.arange(chunks, dtype=jnp.int32)[:, None], (chunks, tc * k))
    cidx = constrain(cidx, ("moe_chunk", None))
    row = constrain(row, ("moe_chunk", None))
    pos = constrain(pos, ("moe_chunk", None))
    buf = jnp.zeros((chunks, e + 1, cap, d), dt)
    buf = constrain(buf, ("moe_chunk", None, None, None))
    src = jnp.repeat(xt, k, axis=1)                       # (C, Tc*k, D)
    src = constrain(src, ("moe_chunk", None, None))
    buf = buf.at[cidx, row, jnp.minimum(pos, cap - 1)].set(src.astype(dt))
    buf = buf[:, :e]
    buf = constrain(buf, ("moe_chunk", "expert", "expert_cap", "act_embed"))

    # batched expert SwiGLU: (C, E, cap, D) x (E, D, F)
    wg = params["w_gate"].astype(dt)
    wu = params["w_up"].astype(dt)
    wd = params["w_down"].astype(dt)
    h = jax.nn.silu(jnp.einsum("cend,edf->cenf", buf, wg))
    h = h * jnp.einsum("cend,edf->cenf", buf, wu)
    h = constrain(h, ("moe_chunk", "expert", "expert_cap", None))
    out_buf = jnp.einsum("cenf,efd->cend", h, wd)
    out_buf = constrain(out_buf,
                        ("moe_chunk", "expert", "expert_cap", "act_embed"))

    # gather back + weighted combine; dropped slots contribute zero
    gathered = out_buf[cidx, jnp.minimum(expert, e - 1),
                       jnp.minimum(pos, cap - 1)]
    gathered = jnp.where(keep[..., None], gathered, 0)
    weights = top_p.reshape(chunks, tc * k).astype(dt)
    comb = (gathered * weights[..., None]).reshape(chunks, tc, k, d).sum(2)
    return comb.reshape(b, s, d), aux.astype(jnp.float32)


# ---------------------------------------------------------- shard_map mode
def _moe_token_slab(router, wg, wu, wd, xt: Array, cfg: ModelConfig
                    ) -> tuple[Array, Array]:
    """Dispatch+experts+combine for a LOCAL token slab (T, D); no sharding
    annotations (runs inside shard_map, where everything is device-local)."""
    t, d = xt.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = int(cfg.capacity_factor * t * k / e)
    cap = max(8, -(-cap // 8) * 8)
    dt = xt.dtype

    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.float32)
    f_e = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)

    flat_oh = onehot.reshape(t * k, e)
    pos = jnp.sum((jnp.cumsum(flat_oh, axis=0) - flat_oh) * flat_oh,
                  axis=-1).astype(jnp.int32)
    expert = top_e.reshape(t * k)
    keep = pos < cap
    row = jnp.where(keep, expert, e)

    buf = jnp.zeros((e + 1, cap, d), dt)
    src = jnp.repeat(xt, k, axis=0)
    buf = buf.at[row, jnp.minimum(pos, cap - 1)].set(src.astype(dt))
    buf = buf[:e]
    h = jax.nn.silu(jnp.einsum("end,edf->enf", buf, wg.astype(dt)))
    h = h * jnp.einsum("end,edf->enf", buf, wu.astype(dt))
    out_buf = jnp.einsum("enf,efd->end", h, wd.astype(dt))
    gathered = out_buf[jnp.minimum(expert, e - 1), jnp.minimum(pos, cap - 1)]
    gathered = jnp.where(keep[:, None], gathered, 0)
    weights = top_p.reshape(t * k).astype(dt)
    comb = (gathered * weights[:, None]).reshape(t, k, d).sum(axis=1)
    return comb, aux.astype(jnp.float32)


def _moe_shard_map(params: dict, x: Array, cfg: ModelConfig, mesh
                   ) -> tuple[Array, Array]:
    """Manual-SPMD MoE (§Perf #B4): GSPMD cannot partition the batched
    dispatch scatter (B1–B3 all regressed), so take manual control:

      * tokens arrive (batch x data-axes, seq x model) sharded — each
        device routes ITS tokens through ITS OWN capacity buffer, fully
        locally (scatter/gather never cross devices);
      * expert weights live (expert x model, embed x data) sharded and are
        all-gathered per layer (the FSDP pattern — bf16 weight gathers are
        the ONLY dispatch collective; gradients transpose to
        reduce-scatters automatically).
    """
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    b_sz, s_sz, _ = x.shape
    data_n = 1
    for a in batch_axes:
        data_n *= mesh.shape[a]
    model_n = mesh.shape["model"] if "model" in names else 1
    # adapt to the actual shape: decode has S=1 (can't shard seq); big
    # decode batches shard over (data, model) instead
    seq_axis = "model" if ("model" in names and s_sz % model_n == 0
                           and s_sz > 1) else None
    if seq_axis is None and "model" in names \
            and b_sz % (data_n * model_n) == 0:
        batch_axes = batch_axes + ("model",)
    elif b_sz % max(data_n, 1) != 0:
        batch_axes = ()
    dt = jnp.dtype(cfg.dtype)

    def local(router, wg, wu, wd, x_loc):
        # reconstruct full weights (bf16) from their (model, data) shards —
        # gather ONLY over axes each array is actually split on (in_specs)
        wg, wu, wd = wg.astype(dt), wu.astype(dt), wd.astype(dt)
        router = router.astype(jnp.float32)
        if "model" in names:
            wg = jax.lax.all_gather(wg, "model", axis=0, tiled=True)
            wu = jax.lax.all_gather(wu, "model", axis=0, tiled=True)
            wd = jax.lax.all_gather(wd, "model", axis=0, tiled=True)
        if "data" in names:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
            router = jax.lax.all_gather(router, "data", axis=0, tiled=True)
        bl, sl, d = x_loc.shape
        comb, aux = _moe_token_slab(router, wg, wu, wd,
                                    x_loc.reshape(bl * sl, d), cfg)
        for ax in (*batch_axes, *((seq_axis,) if seq_axis else ())):
            aux = jax.lax.pmean(aux, ax)
        return comb.reshape(bl, sl, d), aux

    in_specs = (
        P(*(("data",) if "data" in names else (None,))),    # router (D, E)
        P("model" if "model" in names else None,
          "data" if "data" in names else None, None),       # wg (E, D, F)
        P("model" if "model" in names else None,
          "data" if "data" in names else None, None),       # wu
        P("model" if "model" in names else None, None,
          "data" if "data" in names else None),             # wd (E, F, D)
        P(batch_axes if batch_axes else None, seq_axis, None),  # x
    )
    out_specs = (P(batch_axes if batch_axes else None, seq_axis, None), P())
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(params["router"], params["w_gate"], params["w_up"],
              params["w_down"], x)
