"""ICI data-plane programs for single-controller deployments.

The task runtime's multi-process data plane rides the comm engine's
PK_DEVICE rendezvous (native/comm.cpp + device/tpu.py).  When ONE process
controls several devices — a TPU pod slice under a single jax client, or
the 8-virtual-device CPU test mesh — tile movement between devices should
never touch the host at all.  This module provides that path:

- `device_transfer(arr, dst)`: direct device-to-device copy.  On a TPU
  slice `jax.device_put` between devices of one client is a DMA over
  ICI; on the CPU test platform it is a buffer copy.  No host round-trip
  in either case.
- `PermuteEngine`: cached per-(shape, dtype, shift) collective-permute
  executables over a mesh axis — the bulk neighbor-exchange program
  (reference analog: the chain broadcast topology's rank+1 walk,
  parsec/remote_dep.c:43, moved from message passing into one compiled
  XLA collective on ICI).  jit caching makes each (shape, shift) compile
  exactly once, the executable-cache discipline the reference applies to
  GPU kernels (cuda_find_incarnation, device_cuda_module.c:175).
- `TransferSessionPool`: persistent per-peer cross-process transfer
  sessions (jax.experimental.transfer connections).  A connection is an
  endpoint handshake plus transport setup — ~100 ms class on real links
  — so it is established ONCE per (local server, peer address) pair and
  reused by every later pull; the pool records the setup cost per peer
  so benchmarks can report first-transfer setup separately from the
  steady-state per-transfer latency.
"""
import threading
import time
from functools import partial
from typing import Dict, Tuple

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def device_transfer(arr, dst_device):
    """Move a device array to another device of the same client (ICI DMA
    on a TPU slice; never stages through host memory)."""
    return jax.device_put(arr, dst_device)


class PermuteEngine:
    """Cached ring-permute programs over one mesh axis.

    permute(x, shift) rotates the shards of `x` (sharded on `shard_dim`
    along `axis`) by `shift` positions.  Each distinct (shift, ndim,
    shard_dim) builds one jitted program; XLA then caches per shape/dtype
    — repeated exchanges (ring attention steps, halo swaps) re-dispatch
    the same executable.
    """

    def __init__(self, mesh: Mesh, axis: str):
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self._progs: Dict[Tuple, object] = {}

    def _prog(self, shift: int, ndim: int, shard_dim: int):
        key = (shift, ndim, shard_dim)
        f = self._progs.get(key)
        if f is None:
            spec = [None] * ndim
            spec[shard_dim] = self.axis
            pspec = P(*spec)
            perm = [(i, (i + shift) % self.n) for i in range(self.n)]

            def body(xs):
                return lax.ppermute(xs, self.axis, perm)

            f = jax.jit(jax.shard_map(body, mesh=self.mesh,
                                      in_specs=pspec, out_specs=pspec,
                                      check_vma=False))
            self._progs[key] = f
        return f

    def permute(self, x, shift: int = 1, shard_dim: int = 0):
        return self._prog(shift % self.n, x.ndim, shard_dim)(x)

    def exchange(self, x, shard_dim: int = 0):
        """Bidirectional halo exchange: returns (from_prev, from_next) —
        each device sees its ring neighbors' shards (stencil/ring-
        attention building block)."""
        return (self.permute(x, 1, shard_dim),
                self.permute(x, self.n - 1, shard_dim))

    def shard(self, x, shard_dim: int = 0):
        """Lay a host array onto the mesh axis (sharded on shard_dim)."""
        spec = [None] * x.ndim
        spec[shard_dim] = self.axis
        return jax.device_put(x, NamedSharding(self.mesh, P(*spec)))


class TransferSessionPool:
    """Persistent per-peer transfer-plane sessions.

    jax.experimental.transfer connections carry the cross-process
    device-to-device pulls of the PK_DEVICE data plane (device/tpu.py).
    Establishing one is endpoint negotiation + transport setup — the
    fixed cost that made cold per-transfer numbers ~100 ms class — so a
    connection is made ONCE per (server, peer address) pair and reused
    for every later pull.  The pool records establishment cost per peer
    (`setup_ms`) separately from use counts, which is exactly the split
    the transfer-economics harness reports: first-transfer setup vs
    steady-state per-transfer latency.

    Thread-safe: pulls arrive on the comm thread while probes run on
    the caller's thread.  A lost race establishes two connections and
    keeps the first registered (the loser is dropped; connections are
    cheap to leak once, unlike per-pull setup).

    ptc-topo: when the caller knows the peer's RANK it passes it to
    get(); the pool classes the session against the process topology
    model (comm/topology.py) and reports setup cost per link class —
    on a two-island mesh the ~100 ms establishment is expected to
    cluster by class, and `stats()["by_class"]` makes that visible.
    """

    def __init__(self, topo=None, my_rank: int = 0):
        self._lock = threading.Lock()
        self._conns: Dict[str, object] = {}
        self._setup_ms: Dict[str, float] = {}
        self._cls: Dict[str, str] = {}
        self._established = 0
        self._reused = 0
        self._topo = topo
        self._my_rank = int(my_rank)

    def _class_of(self, peer_rank) -> str:
        if peer_rank is None:
            return "ici"
        topo = self._topo
        if topo is None:
            from .topology import default_topology
            topo = self._topo = default_topology(
                max(self._my_rank, int(peer_rank)) + 1)
        return topo.class_of(self._my_rank, int(peer_rank))

    def get(self, server, addr: str, peer_rank=None):
        """The session for `addr`, establishing it on first use."""
        with self._lock:
            conn = self._conns.get(addr)
            if conn is not None:
                self._reused += 1
                return conn
        t0 = time.perf_counter()
        conn = server.connect(addr)
        dt_ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            prior = self._conns.get(addr)
            if prior is not None:  # lost an establishment race
                self._reused += 1
                return prior
            self._conns[addr] = conn
            self._setup_ms[addr] = dt_ms
            self._cls[addr] = self._class_of(peer_rank)
            self._established += 1
        return conn

    def stats(self) -> dict:
        with self._lock:
            by_class: Dict[str, dict] = {}
            for addr, ms in self._setup_ms.items():
                c = by_class.setdefault(self._cls.get(addr, "ici"),
                                        {"peers": 0, "setup_ms": 0.0})
                c["peers"] += 1
                c["setup_ms"] += ms
            return {
                "peers": len(self._conns),
                "established": self._established,
                "reused": self._reused,
                "setup_ms": dict(self._setup_ms),
                "by_class": by_class,
            }
