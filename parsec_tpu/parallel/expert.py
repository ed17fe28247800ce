"""Expert parallelism: MoE feed-forward with experts sharded over an
`ep` mesh axis.

SURVEY.md §2.10 marks expert parallelism absent from the reference; its
nearest building blocks are the irregular `hash_datadist` keyed
distribution (parsec/data_dist/hash_datadist.h:20-41 — our
parsec_tpu.data.HashDatadist) and DTD dynamic tasks.  The TPU-native
version is the GShard dispatch/combine pattern: capacity-bounded top-k
routing, one all-to-all to ship token slices to expert owners
(= redistribute.jdf's collection->collection reshard), batched expert
matmuls on the MXU, and the inverse all-to-all home.

Everything is static-shaped (capacity C fixed at trace time) so XLA can
tile the expert einsums; overflow tokens are dropped, exactly as GShard
capacity semantics prescribe.
"""
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import Mesh, PartitionSpec as P


def _dispatch_combine(logits, k: int, capacity: int):
    """Top-k capacity-bounded routing tables.

    logits: [T, E] -> dispatch [T, E, C] (0/1), combine [T, E, C] (gate
    weights).  Tokens beyond an expert's capacity are dropped (their
    combine rows are zero)."""
    t_, e_ = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idxs = lax.top_k(probs, k)                    # [T, k]
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)

    dispatch = jnp.zeros((t_, e_, capacity), jnp.float32)
    combine = jnp.zeros((t_, e_, capacity), jnp.float32)
    counts = jnp.zeros((e_,), jnp.int32)
    for s in range(k):                                  # k is static, tiny
        e_sel = idxs[:, s]                              # [T]
        onehot = jax.nn.one_hot(e_sel, e_, dtype=jnp.int32)
        # position of each token within its expert's buffer
        pos = counts[None, :] + jnp.cumsum(onehot, axis=0) - onehot
        pos_t = jnp.sum(pos * onehot, axis=-1)          # [T]
        keep = (pos_t < capacity).astype(jnp.float32)
        slot = (jax.nn.one_hot(e_sel, e_) *
                keep[:, None])[:, :, None] * jax.nn.one_hot(
                    jnp.minimum(pos_t, capacity - 1), capacity)[:, None, :]
        dispatch = dispatch + slot
        combine = combine + slot * vals[:, s, None, None]
        counts = counts + jnp.sum(onehot, axis=0)
    return dispatch, combine


def moe_ffn(x, w_gate, w_up, w_down, mesh: Mesh, axis: str = "ep",
            k: int = 2, capacity_factor: float = 1.25,
            activation=jax.nn.gelu,
            capacity: Optional[int] = None,
            x_spec: Optional[P] = None):
    """Mixture-of-experts FFN, expert-parallel over mesh axis `axis`.

    x:      [B, S, D]   batch-sharded over `axis` (or per `x_spec` when
                        batch/sequence are additionally dp/sp-sharded —
                        routing is then local per shard, hierarchical EP)
    w_gate: [D, E]      replicated router
    w_up:   [E, D, F]   experts sharded over `axis` (E = n * E_local)
    w_down: [E, F, D]   experts sharded over `axis`
    Returns [B, S, D] with x's sharding.
    """
    n = mesh.shape[axis]
    e_total = w_up.shape[0]
    if e_total % n != 0:
        raise ValueError(f"n_experts ({e_total}) must divide over "
                         f"'{axis}' size ({n})")
    xs = x_spec if x_spec is not None else P(axis, None, None)
    # local token count after every sharded dim of x_spec is applied
    shard = 1
    for ax in xs[:2]:
        if ax is not None:
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shard *= mesh.shape[a]
    b, s_len, d = x.shape
    t_loc = max(1, (b * s_len) // shard)
    cap = capacity if capacity is not None else max(
        1, int(capacity_factor * k * t_loc / e_total))

    ws = P(axis, None, None)

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(xs, P(None, None), ws, ws),
             out_specs=xs)
    def _moe(x_loc, wg, wu_loc, wd_loc):
        bl, sl, dm = x_loc.shape
        tok = x_loc.reshape(bl * sl, dm)
        dispatch, combine = _dispatch_combine(tok @ wg, k, cap)
        # [T,E,C] x [T,D] -> [E,C,D]: per-expert send buffers
        send = jnp.einsum("tec,td->ecd", dispatch, tok)
        # ship slices to expert owners: [E, C, D] -> [E_loc, n*C, D]
        recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=1,
                              tiled=True)
        h = activation(jnp.einsum("ecd,edf->ecf", recv, wu_loc))
        out = jnp.einsum("ecf,efd->ecd", h, wd_loc)
        # inverse all-to-all: [E_loc, n*C, D] -> [E, C, D]
        back = lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                              tiled=True)
        y = jnp.einsum("tec,ecd->td", combine, back)
        return y.reshape(bl, sl, dm).astype(x_loc.dtype)

    return _moe(x, w_gate, w_up, w_down)


def moe_ffn_reference(x, w_gate, w_up, w_down, k: int = 2,
                      activation=jax.nn.gelu):
    """Dense single-device oracle: every token runs its top-k experts with
    no capacity limit."""
    b, s_len, d = x.shape
    tok = x.reshape(-1, d)
    probs = jax.nn.softmax(tok @ w_gate, axis=-1)
    vals, idxs = lax.top_k(probs, k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    h = activation(jnp.einsum("td,edf->tef", tok, w_up))
    outs = jnp.einsum("tef,efd->ted", h, w_down)        # [T, E, D]
    y = jnp.zeros_like(tok)
    for s in range(k):
        y = y + vals[:, s, None] * jnp.take_along_axis(
            outs, idxs[:, s, None, None], axis=1)[:, 0]
    return y.reshape(b, s_len, d)
