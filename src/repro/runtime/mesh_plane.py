"""Mesh plane: device-mesh data parallelism for the lattice and the store.

Everything else in the reproduction runs on ONE device: the schemes x
nets x C x policies lattice is a single-device vmap nest
(`desim._lattice_jit`) and `serve_replicated`'s C replicas are simulated
compute units sharing one program. This module maps both onto a real JAX
device mesh with `shard_map` (DESIGN.md §11):

* ``simulate_lattice_sharded`` — shards the OUTERMOST lattice axis (the
  nets x policies cell product, padded up to a multiple of the mesh
  size) across a 1-axis ``("data",)`` mesh. Every device runs the SAME
  `desim._simulate_point` trace over its cell slice, so a full sweep
  compiles ONCE and the wall-clock divides by the device count. Lattice
  cells are independent simulations — no cross-device communication at
  all on this plane.

* ``step_replicated_sharded`` / ``serve_replicated_sharded`` — place the
  (C,) replica axis of `step_fetch_replicated` on the mesh: per-replica
  sequence state, NIC banks, and telemetry live device-local, and the
  SHARED memory-module channel bank is merged at the fabric boundary
  with `fabric.reduce_deltas` (base + psum of per-device deltas). Byte
  ledgers are additive, so two-endpoint byte conservation stays exact;
  cross-device channel contention lands at the step boundary instead of
  per-request (each device's in-step view sees only its own queueing —
  the documented relaxation of the sharded store).

Both paths fall back BIT-IDENTICALLY to the existing vmap paths on a
1-device mesh: the lattice body is the same `_simulate_point` under a
re-nested vmap, and a 1-device psum is the identity. Pinned by
`tests/test_mesh_plane.py` against the seed golden capture and the
replicated-store equivalence tests; the 8-device equivalence check lives
in `tests/test_distributed.py` (forced host devices).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fabric
from repro.core.daemon_store import (KVStoreConfig, ReplicatedKVStoreState,
                                     step_fetch_replicated)
from repro.launch.mesh import make_data_mesh
from repro.sim.desim import (_lattice_inputs, _nest_lattice,
                             _simulate_point)

__all__ = ["simulate_lattice_sharded", "sharded_lattice_cache_size",
           "shard_replicated_state", "step_replicated_sharded",
           "sharded_store_cache_size", "serve_replicated_sharded",
           "make_data_mesh"]


# ------------------------------------------------------------ lattice plane
def _cell_stacks(stacked_nets, pols_arr, n_nets, n_pols, n_pad):
    """Flatten the nets x policies axes into one leading CELL axis
    (cell k = net k // P, policy k % P), padded to `n_pad` cells by
    repeating cell 0 (computed twice, discarded at unpad — padding never
    changes results, only fills idle devices)."""
    idx = list(range(n_nets * n_pols)) + [0] * (n_pad - n_nets * n_pols)
    idx = jnp.asarray(idx, jnp.int32)
    nets_c = jax.tree.map(
        lambda a: jnp.repeat(a, n_pols, axis=0)[idx], stacked_nets)
    pols_c = jax.tree.map(
        lambda a: jnp.concatenate([a] * n_nets, axis=0)[idx], pols_arr)
    return nets_c, pols_c


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _sharded_lattice_jit(cfg, n_pages, telcfg, mesh, tflags, warm_after,
                         trace_arrays, nets_cells, comp_ratio, active_cus,
                         pols_cells):
    """shard_map(cells) o vmap(cell) o vmap(schemes) o vmap(active-C)
    over `desim._simulate_point` — the sharded sibling of
    `desim._lattice_jit`, jitted once per (SimConfig, footprint, trace
    shape, mesh, axis lengths, TelemetryConfig)."""
    point = partial(_simulate_point, cfg, n_pages, telcfg)
    over_cus = jax.vmap(point, in_axes=(None, None, None, None, None,
                                        0, None))
    over_schemes = jax.vmap(over_cus, in_axes=(0, None, None, None, 0,
                                               None, None))

    def body(tf, wa, tr, nets_loc, cr, cus, pols_loc):
        one_cell = lambda net, pol: over_schemes(tf, wa, tr, net, cr,
                                                 cus, pol)
        return jax.vmap(one_cell)(nets_loc, pols_loc)   # (cells_loc, S, C)

    # check_vma=False: the replication checker mis-tracks scan carries
    # (jax#21427-style); every output is P("data")-sharded anyway so no
    # replication claim is being made
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P(), P(), P("data")),
        out_specs=P("data"), check_vma=False)(
        tflags, warm_after, trace_arrays, nets_cells, comp_ratio,
        active_cus, pols_cells)


def sharded_lattice_cache_size() -> int:
    """Compiled sharded-lattice variants so far (compile-count pin)."""
    return _sharded_lattice_jit._cache_size()


def simulate_lattice_sharded(schemes, cfg, trace, nets, comp_ratio,
                             mesh=None, warm_frac: float = 0.3,
                             active_cus=None, policies=None,
                             telemetry_cfg=None):
    """`desim.simulate_lattice`, data-parallel over a device mesh.

    Same arguments and same nested-result contract as
    `desim.simulate_lattice`, plus `mesh` — a 1-axis ``("data",)`` mesh
    (default: `make_data_mesh()` over every visible device). The nets x
    policies product is flattened into cells, padded up to a multiple of
    the mesh size (by repeating cell 0; the pad is dropped before
    nesting), and each device sweeps its cell slice through the same
    `_simulate_point` scan the vmap path traces. ONE compile per
    (SimConfig, trace shape, mesh, axis lengths); on a 1-device mesh the
    results are bit-identical to `simulate_lattice`.
    """
    if mesh is None:
        mesh = make_data_mesh()
    schemes = list(schemes)      # may be a generator: list ONCE
    (tflags, warm_after, arrays, stacked, cr, cus_arr, pols_arr, telcfg,
     squeeze_cu, squeeze_pol, n_cus, n_pols) = _lattice_inputs(
        schemes, cfg, trace, nets, comp_ratio, warm_frac, active_cus,
        policies, telemetry_cfg)
    n_schemes, n_nets = len(schemes), len(nets)
    d = mesh.devices.size
    ncells = n_nets * n_pols
    n_pad = -(-ncells // d) * d
    nets_c, pols_c = _cell_stacks(stacked, pols_arr, n_nets, n_pols,
                                  n_pad)
    res = _sharded_lattice_jit(cfg, trace.n_pages, telcfg, mesh, tflags,
                               warm_after, arrays, nets_c, cr, cus_arr,
                               pols_c)
    # (cells_pad, S, C) -> drop pad -> (N, P, S, C) -> (S, N, C, P),
    # the `_lattice_jit` layout `_nest_lattice` expects
    res = {k: jnp.transpose(
        v[:ncells].reshape((n_nets, n_pols) + v.shape[1:]), (2, 0, 3, 1))
        for k, v in res.items()}
    return _nest_lattice(res, n_schemes, n_nets, n_cus, n_pols,
                         squeeze_cu, squeeze_pol)


# -------------------------------------------------------------- store plane
# shard_map specs for a ReplicatedKVStoreState: per-replica state is
# device-local (sequence leaves and NIC banks carry leading (C*B,) /
# (C,) axes), the shared module bank and the step clock are replicated.
# The NIC bank needs per-leaf specs: its LinkModel schedule leaves carry
# the unit axis on dim 1 ((K, C) sched_mult/health) and `sched_t` (K,)
# has no unit axis — those can't take the bank-wide leading-axis spec.
_NIC_SPECS = fabric.FabricState(
    line_busy=P("data"), page_busy=P("data"), wb_busy=P("data"),
    line_bytes=P("data"), page_bytes=P("data"), wb_bytes=P("data"),
    ratio=P("data"), line_rate=P("data"), page_rate=P("data"),
    link=fabric.LinkModel(bw=P("data"), sched_t=P(),
                          sched_mult=P(None, "data"),
                          health=P(None, "data")))
_STATE_SPECS = ReplicatedKVStoreState(
    seqs=P("data"), fab=P(), nic=_NIC_SPECS, clock=P())


def shard_replicated_state(state: ReplicatedKVStoreState, mesh
                           ) -> ReplicatedKVStoreState:
    """Place a replicated store's state on the mesh: replica-major
    sequence leaves and NIC banks split along ``"data"`` (the global
    replica count must divide the mesh size evenly), shared fabric +
    clock replicated. Telemetry (inside `seqs`) shards with its tenant."""
    c, d = state.num_replicas, mesh.devices.size
    if c % d:
        raise ValueError(f"num_replicas={c} must divide evenly across "
                         f"{d} mesh devices")
    shard = lambda spec: (lambda x: jax.device_put(
        x, NamedSharding(mesh, spec)))
    return ReplicatedKVStoreState(
        seqs=jax.tree.map(shard(P("data")), state.seqs),
        fab=jax.tree.map(shard(P()), state.fab),
        nic=jax.tree.map(lambda spec, x: shard(spec)(x), _NIC_SPECS,
                         state.nic),
        clock=shard(P())(state.clock))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _sharded_store_jit(cfg, mesh, active, state, remote_k, remote_v,
                       needed_pages, needed_offsets, needed_writes):
    """One sharded replicated decode step: each device runs the existing
    `step_fetch_replicated` on its local replica slice (NIC gate forced
    to the GLOBAL `active`), then the shared module bank is merged at the
    fabric boundary with `fabric.reduce_deltas` — the one cross-device
    communication point, exactly the disaggregated-memory topology."""
    def body(st, rk, rv, need, offs, writes):
        base = st.fab
        st, k, v, hit = step_fetch_replicated(st, cfg, rk, rv, need,
                                              offs, writes,
                                              active=active)
        st = st._replace(fab=fabric.reduce_deltas(base, st.fab, "data"))
        return st, k, v, hit

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(_STATE_SPECS, P(), P(), P("data"), P("data"),
                  P("data")),
        out_specs=(_STATE_SPECS, P("data"), P("data"), P("data")),
        check_vma=False)(
        state, remote_k, remote_v, needed_pages, needed_offsets,
        needed_writes)


def sharded_store_cache_size() -> int:
    """Compiled sharded-store variants so far (compile-count pin)."""
    return _sharded_store_jit._cache_size()


def step_replicated_sharded(state: ReplicatedKVStoreState,
                            cfg: KVStoreConfig, mesh, remote_k, remote_v,
                            needed_pages, needed_offsets=None,
                            needed_writes=None):
    """`step_fetch_replicated` with the (C,) replica axis on the mesh.

    `needed_pages` / offsets / writes are (C, B, R) replica-major like
    the vmap path; `state` should be placed with
    `shard_replicated_state` first (jit reshards on the fly otherwise).
    The NIC gate uses the GLOBAL replica count — a device stepping a
    single local replica of a C=8 deployment still pays its NIC leg.
    On a 1-device mesh the psum in the fabric merge is the identity and
    the step is bit-identical to `step_fetch_replicated`.

    Returns (state, k (C,B,R,page,KV,D), v, served_local (C,B,R) bool).
    """
    c, b, r = needed_pages.shape
    offs = (jnp.zeros((c, b, r), jnp.int32) if needed_offsets is None
            else jnp.asarray(needed_offsets))
    writes = (jnp.zeros((c, b, r), bool) if needed_writes is None
              else jnp.asarray(needed_writes))
    return _sharded_store_jit(cfg, mesh, c > 1, state, remote_k,
                              remote_v, needed_pages, offs, writes)


def serve_replicated_sharded(params, cfg, prompts, scfg, store_cfg,
                             num_replicas: int, mesh=None, **kw):
    """`serve_loop.serve_replicated` with the replica axis on a device
    mesh (default: `make_data_mesh()` over every visible device) — same
    arguments, same (tokens (C, B, T), ledger) contract."""
    from repro.runtime.serve_loop import serve_replicated
    if mesh is None:
        mesh = make_data_mesh()
    return serve_replicated(params, cfg, prompts, scfg, store_cfg,
                            num_replicas, mesh=mesh, **kw)
