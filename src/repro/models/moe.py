"""Token-choice top-k Mixture-of-Experts with GROUP-LOCAL fixed-capacity
dispatch.

GShard-style: router -> top-k -> rank-within-expert via cumsum -> scatter into
a capacity-bounded buffer -> batched expert GEMMs -> weighted combine.  All
shapes are static, so the layer lowers cleanly under pjit.

Dispatch locality: tokens are split into G groups, each with its own capacity
and its own scatter.  G maps onto the data-parallel axes (G = dp size), so
the dispatch buffer carries a leading sharded dim and the scatter/gather stay
entirely shard-local — the global-dispatch formulation (G=1) makes GSPMD
replicate the (E, C, D) buffer on every chip and all-reduce it, which the
§Perf hillclimb measured at ~10 TB/chip/step on mixtral train_4k.  Per-group
capacity (= per-device dropping) is the standard large-scale semantics
(GShard, Switch, DeepSeek-V2).  On CPU tests there is no mesh, G=1, and the
semantics reduce to classic global dispatch.

Expert weights shard as EP over the model axis when E divides it, else TP
over the expert hidden dim (see distributed/sharding.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.context import batch_axes, get_mesh, shard
from repro.models.layers import norm_apply, norm_init, normal_init


def moe_init(key, cfg: ArchConfig):
    m = cfg.moe
    D, E, F = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 4)
    p = {
        "norm": norm_init(cfg, D),
        "router": normal_init(ks[0], (D, E)),
        "w_gate": normal_init(ks[1], (E, D, F)),
        "w_up": normal_init(ks[2], (E, D, F)),
        "w_down": normal_init(ks[3], (E, F, D)),
    }
    if cfg.post_norms:
        p["post_norm"] = norm_init(cfg, D)
    return p


def _dispatch_groups(n_tokens: int) -> int:
    """Number of local-dispatch groups: the DP-shard count when it divides
    the token count (so group boundaries align with shard boundaries)."""
    mesh = get_mesh()
    if mesh is None:
        return 1
    g = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            g *= mesh.shape[a]
    return g if (g > 1 and n_tokens % g == 0) else 1


def moe_apply(x, p, cfg: ArchConfig, compute_dtype, return_aux: bool = False):
    """Dispatch wrapper: shard_map the MoE block over the DP axes (token
    locality enforced manually — GSPMD replicates data-dependent scatters),
    leaving the model axis on auto so expert-weight TP/EP still partitions
    inside.  Falls back to the GSPMD global path off-mesh / non-divisible."""
    with jax.named_scope("moe"):
        mesh = get_mesh()
        ba = batch_axes()
        B, S = x.shape[0], x.shape[1]
        dp = 1
        for a in ba:
            dp *= mesh.shape[a]
        # shard_map replicates expert weights across DP (gathered once per call):
        # profitable only when enough tokens amortize it — decode steps (a few
        # tokens/shard) measured 0.3x WORSE, so they stay on the global path.
        tokens_per_shard = B * S // max(dp, 1)
        if (mesh is None or not ba or B % dp != 0 or return_aux
                or tokens_per_shard < 256):
            # below the amortization threshold grouping also hurts (the grouped
            # rank-4 expert GEMMs make GSPMD gather W): plain global dispatch
            return _moe_apply_global(x, p, cfg, compute_dtype, return_aux,
                                     groups=1)

        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            lambda xl, pl: _moe_apply_global(xl, pl, cfg, compute_dtype, False,
                                             local=True),
            mesh=mesh,
            in_specs=(P(ba, None, None), P()),
            out_specs=P(ba, None, None),
            axis_names=frozenset(ba),            # manual over DP; model stays auto
            check_vma=False,
        )
        return fn(x, p)


def _moe_apply_global(x, p, cfg: ArchConfig, compute_dtype,
                      return_aux: bool = False, local: bool = False,
                      groups=None):
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k

    h = norm_apply(x, p["norm"], cfg).astype(compute_dtype)
    Nt = B * S
    # inside the shard_map body shapes are already per-shard: no further
    # grouping, and no sharding constraints (dp axes are manual there)
    G = 1 if local else (groups if groups is not None else _dispatch_groups(Nt))
    if G == 1:
        # flat path: no leading group dim (a unit G dim was measured to break
        # both the token-dim sharding and GSPMD's expert-GEMM strategy)
        return _moe_flat(x, h, p, cfg, compute_dtype, return_aux, local)
    NtG = Nt // G
    ba = None if local else (batch_axes() or None)
    sh = (lambda t, *spec: t) if local else shard
    hg = sh(h.reshape(G, NtG, D), ba, None, None)           # (G, NtG, D)

    logits = (hg @ p["router"].astype(compute_dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (G, NtG, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)           # (G, NtG, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = int(max(8, -(-NtG * K // E) * m.capacity_factor))
    cap = min(cap, NtG)

    eidx = gate_idx.reshape(G, NtG * K)                     # (G, NtG*K)
    onehot = jax.nn.one_hot(eidx, E, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=1) - onehot              # rank within group
    pos = jnp.take_along_axis(rank, eidx[..., None], axis=2)[..., 0]
    keep = pos < cap
    dst = jnp.where(keep, eidx * cap + pos, E * cap)        # overflow row = drop

    src = jnp.repeat(hg, K, axis=1)                         # (G, NtG*K, D)
    gi = jnp.arange(G)[:, None]
    buf = jnp.zeros((G, E * cap + 1, D), compute_dtype).at[gi, dst].set(src)
    buf = sh(buf[:, :-1].reshape(G, E, cap, D), ba, None, None, None)

    wg = p["w_gate"].astype(compute_dtype)
    wu = p["w_up"].astype(compute_dtype)
    wd = p["w_down"].astype(compute_dtype)
    if G == 1:
        # rank-3 einsums: a leading unit G dim was measured to flip GSPMD's
        # expert-GEMM strategy from partial-sum+AR to a full W all-gather
        b3 = buf[0]
        a = jax.nn.silu(jnp.einsum("ecd,edf->ecf", b3, wg))
        u = jnp.einsum("ecd,edf->ecf", b3, wu)
        out = jnp.einsum("ecf,efd->ecd", a * u, wd)[None]
    else:
        a = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, wg))
        u = jnp.einsum("gecd,edf->gecf", buf, wu)
        out = jnp.einsum("gecf,efd->gecd", a * u, wd)
    out = sh(out, ba, None, None, None)

    out_flat = jnp.concatenate(
        [out.reshape(G, E * cap, D),
         jnp.zeros((G, 1, D), compute_dtype)], axis=1)      # (G, E*cap+1, D)
    gathered = jnp.take_along_axis(
        out_flat, dst[..., None].astype(jnp.int32), axis=1)  # (G, NtG*K, D)
    weighted = gathered.astype(jnp.float32) * gate_vals.reshape(G, -1)[..., None]
    y = weighted.reshape(G, NtG, K, D).sum(axis=2).reshape(B, S, D)

    if cfg.post_norms:
        y = norm_apply(y.astype(x.dtype), p["post_norm"], cfg).astype(jnp.float32)

    result = x + y.astype(x.dtype)
    if return_aux:
        # load-balancing auxiliary loss (Switch-style): E * sum_e f_e * p_e
        frac_tokens = jnp.mean(
            jax.nn.one_hot(gate_idx[..., 0].reshape(-1), E, dtype=jnp.float32),
            axis=0)
        mean_probs = probs.reshape(-1, E).mean(axis=0)
        aux = E * jnp.sum(frac_tokens * mean_probs)
        return result, aux
    return result


def _moe_flat(x, h, p, cfg: ArchConfig, compute_dtype,
              return_aux: bool = False, local: bool = False):
    """Classic global token-choice dispatch on a flat (Nt, D) token array —
    the exact pre-grouping formulation (decode / tiny batches / shard_map
    interior)."""
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    Nt = B * S
    # no token-dim constraint here: forcing it on decode-scale token sets was
    # measured to inject per-layer reshard chatter (a2a/permute); GSPMD
    # propagates the upstream activation sharding
    hf = h.reshape(-1, D)                                  # (Nt, D)
    logits = (hf @ p["router"].astype(compute_dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                # (Nt, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)          # (Nt, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = int(max(8, -(-Nt * K // E) * m.capacity_factor))
    cap = min(cap, Nt)

    eidx = gate_idx.reshape(-1)                            # (Nt*K,)
    onehot = jax.nn.one_hot(eidx, E, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(rank, eidx[:, None], axis=1)[:, 0]
    keep = pos < cap
    dst = jnp.where(keep, eidx * cap + pos, E * cap)       # overflow row = drop

    src_rows = jnp.repeat(hf, K, axis=0)                   # (Nt*K, D)
    buf = jnp.zeros((E * cap + 1, D), compute_dtype).at[dst].set(src_rows)
    buf = buf[:-1].reshape(E, cap, D)

    a = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                               p["w_gate"].astype(compute_dtype)))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(compute_dtype))
    out = jnp.einsum("ecf,efd->ecd", a * u, p["w_down"].astype(compute_dtype))

    out_flat = jnp.concatenate(
        [out.reshape(E * cap, D), jnp.zeros((1, D), compute_dtype)], axis=0)
    gathered = out_flat[dst]                               # (Nt*K, D)
    weighted = gathered.astype(jnp.float32) * gate_vals.reshape(-1)[:, None]
    y = weighted.reshape(Nt, K, D).sum(axis=1).reshape(B, S, D)

    if cfg.post_norms:
        y = norm_apply(y.astype(x.dtype), p["post_norm"], cfg).astype(jnp.float32)

    result = x + y.astype(x.dtype)
    if return_aux:
        frac_tokens = jnp.mean(
            jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
        mean_probs = probs.mean(axis=0)
        aux = E * jnp.sum(frac_tokens * mean_probs)
        return result, aux
    return result
