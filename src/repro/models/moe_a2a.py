"""Explicit all-to-all MoE dispatch (shard_map) — the §Perf fix for the
collective-bound MoE cells.

Baseline problem (measured, EXPERIMENTS.md §Perf): with tokens sharded on
the batch axes and experts sharded on another axis, XLA lowers the capacity
 -buffer scatter to *replicate-and-all-reduce*: every layer all-reduces the
full (E, C, d) buffer (kimi-k2 prefill: 14.8 TiB/device/step). The classic
fix is the explicit MoE all-to-all:

  per shard: route local tokens into (E, C_l, d) send buckets (local
  scatter), all_to_all over the expert axis -> (E_l, n*C_l, d), run the
  local experts (optionally TP on d_ff with a final psum of the combined
  token outputs), all_to_all back, combine gates locally.

Per-device wire bytes drop from O(E·C·d) all-reduce to O(T_l·k·cf·d)
all-to-all — a ~n_expert_shards x reduction.

Used when a sharding-rules context with a mesh is active and the expert
weights carry no FSDP dim (serving; or training with fsdp=None). Falls back
to the dense formulation otherwise.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .layers import _load_balance_loss


def _axes_tuple(ax) -> Tuple[str, ...]:
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(ax)


def moe_ffn_a2a(x, router_w, w1, w3, w2, *, top_k: int,
                capacity_factor: float, dtype, mesh, token_axes,
                expert_axes, tp_axis: Optional[str]):
    """x: (B, S, d) batch-sharded on token_axes; w1/w3: (E, d, f), w2:
    (E, f, d) with E sharded on expert_axes and optionally f on tp_axis."""
    B, S, d = x.shape
    E = router_w.shape[1]
    tok = _axes_tuple(token_axes)
    exp = _axes_tuple(expert_axes)
    n_e = int(np.prod([mesh.shape[a] for a in exp])) if exp else 1
    if n_e == 1 or E % n_e != 0 or (B % n_e != 0 and tok == exp):
        from .layers import moe_ffn
        return moe_ffn(x, router_w, w1, w3, w2, top_k=top_k,
                       capacity_factor=capacity_factor, dtype=dtype)
    f = w1.shape[-1]
    tp = tp_axis if (tp_axis and tp_axis in mesh.axis_names and
                     f % mesh.shape[tp_axis] == 0 and
                     tp_axis not in exp and tp_axis not in tok) else None

    n_tok = int(np.prod([mesh.shape[a] for a in tok])) if tok else 1
    T_l = (B // n_tok if B % n_tok == 0 else B) * S
    C_l = max(1, int(np.ceil(T_l * top_k / E * capacity_factor)))

    def local(xl, rw, w1l, w3l, w2l):
        Bl, Sl, _ = xl.shape
        Tl = Bl * Sl
        xf = xl.reshape(Tl, d)
        logits = xf.astype(jnp.float32) @ rw.astype(jnp.float32)
        gval, gidx = jax.lax.top_k(logits, top_k)
        gates = jax.nn.softmax(gval, axis=-1)

        flat_e = gidx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(Tl), top_k)
        order = jnp.argsort(flat_e, stable=True)
        se, stt = flat_e[order], flat_t[order]
        idx = jnp.arange(Tl * top_k)
        first = jnp.concatenate([jnp.ones((1,), bool), se[1:] != se[:-1]])
        seg = jax.lax.cummax(jnp.where(first, idx, 0))
        rank = idx - seg
        keep = rank < C_l
        slot = jnp.where(keep, se * C_l + rank, E * C_l)

        send = jnp.zeros((E * C_l, d), dtype).at[slot].set(
            xf[stt].astype(dtype), mode="drop").reshape(E, C_l, d)
        # dispatch: split experts across shards, concat token slices
        recv = send
        for a in exp:
            recv = jax.lax.all_to_all(recv, a, split_axis=0, concat_axis=1,
                                      tiled=True)
        # recv: (E_l, n_e*C_l, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv,
                                   w1l.astype(dtype))) * \
            jnp.einsum("ecd,edf->ecf", recv, w3l.astype(dtype))
        y = jnp.einsum("ecf,efd->ecd", h, w2l.astype(dtype))
        # return path (y is f-partial if TP; combine after token-side sum)
        back = y
        for a in reversed(exp):
            back = jax.lax.all_to_all(back, a, split_axis=1, concat_axis=0,
                                      tiled=True)
        back = back.reshape(E * C_l, d)
        sg = jax.nn.softmax(gval, axis=-1).reshape(-1)[order]
        contrib = jnp.where(keep[:, None],
                            back[jnp.clip(slot, 0, E * C_l - 1)] *
                            sg[:, None].astype(dtype), 0)
        out = jnp.zeros((Tl, d), dtype).at[stt].add(contrib)
        if tp is not None:
            out = jax.lax.psum(out, tp)
        aux = _load_balance_loss(logits, gidx, E)
        aux = jax.lax.pmean(aux, tok) if tok else aux
        return out.reshape(Bl, Sl, d), aux

    batch_ok = B % n_tok == 0 if tok else True
    x_spec = P(tok if batch_ok and tok else None, None, None)
    w_spec = P(exp, None, tp)
    w2_spec = P(exp, tp, None)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(), w_spec, w_spec, w2_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, router_w, w1, w3, w2)
    return out
