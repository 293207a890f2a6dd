"""Batched scheduling kernels — the device-resident decision core.

This replaces the reference's per-task C++ event-loop decisions
(ray: src/ray/raylet/scheduling/cluster_task_manager.cc
ClusterTaskManager::ScheduleAndDispatchTasks + local_task_manager.cc
LocalTaskManager dispatch + scheduling_policy.cc HybridSchedulingPolicy)
with data-parallel passes over the whole pending set per tick:

  1. ready-set:   ready = waiting & (indegree == 0)
  2. assignment:  for each scheduling class (the reference's
                  SchedulingClass — tasks with identical (fn, demand)
                  that can share worker leases), partition the ready
                  tasks over nodes by a vectorized capacity fill:
                  per-node fit counts -> cumsum -> searchsorted.
                  The hybrid policy analog: node 0 ("local") is filled
                  first up to the configured load threshold, then all
                  nodes least-loaded-first.
  3. completion wave: fire CSR edges of newly-done producers and
                  decrement consumer indegrees with one segment-add.

Two interchangeable backends with identical semantics:
  - numpy: low-latency host ticks for small/interactive batches
  - jax:   jit-compiled ticks for large batches and the benchmark
           graphs (runs on the TPU; all O(T+E) ops vectorize onto the
           VPU and the partition math is a handful of tiny reductions)

Array-state conventions shared by both backends and TensorScheduler:
  state   int8  [C]   0=FREE 1=WAITING 3=RUNNING 4=DONE  (2 reserved)
  indeg   int32 [C]   outstanding dependency count
  cls     int32 [C]   scheduling-class index into demands
  demands f32  [K,R]  per-class resource demand vectors
  avail   f32  [N,R]  per-node available resources
  cap     f32  [N,R]  per-node capacities
  node_of int32 [C]   assigned node (-1 = unassigned)
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

FREE, WAITING, RUNNING, DONE = 0, 1, 3, 4


# ======================================================================
# numpy backend
# ======================================================================

def assign_np(ready_idx: np.ndarray, cls: np.ndarray, demands: np.ndarray,
              avail: np.ndarray, cap: np.ndarray,
              threshold: float,
              class_mask: Optional[np.ndarray] = None,
              class_spread: Optional[np.ndarray] = None,
              locality: Optional[np.ndarray] = None,
              outstanding: Optional[np.ndarray] = None,
              spill_depth: int = 0
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Assign ready tasks (by arena index) to nodes.

    class_mask [K,N] bool restricts each scheduling class to a node
    subset (placement groups pin classes to their reserved bundle rows;
    normal classes exclude bundle rows; node-affinity pins to one row).
    class_spread [K] bool disables the hybrid local-node bias for
    SPREAD-strategy classes. None = no restriction / no spread.

    locality [len(ready_idx),N] float scores each ready task's
    candidate nodes by resident-arg-bytes (0 = no input data there).
    A task with any nonzero row prefers its argmax node when feasible;
    if that node is momentarily full it WAITS for it — but only while
    the node has fewer than ``spill_depth`` leases outstanding
    (``outstanding`` [N] int), beyond which the task spills back to
    the normal least-loaded fill so a hot node never serializes the
    cluster. SPREAD classes and placement masks override locality.
    None = pre-locality behavior, byte-for-byte.

    Returns (node_of_ready [len(ready_idx)] int32 with -1 for
    not-assigned-this-tick, updated avail). Mutates nothing.
    """
    avail = avail.copy()
    n_nodes = avail.shape[0]
    out = np.full(len(ready_idx), -1, dtype=np.int32)
    if len(ready_idx) == 0:
        return out, avail

    # a removed node zeroes its capacity; it must never receive tasks —
    # without this, zero-demand tasks see it as the least-loaded node
    alive = cap.any(axis=1)
    ready_cls = cls[ready_idx]
    for c in np.unique(ready_cls):
        members = np.flatnonzero(ready_cls == c)  # positions in ready_idx
        d = demands[c]
        elig = alive if class_mask is None else (alive & class_mask[c])
        spread = bool(class_spread[c]) if class_spread is not None else False
        active = d > 0

        # locality pre-pass: tasks with resident input bytes go to (or
        # wait for) the eligible node holding the most of them; the
        # remainder flows through the normal hybrid fill below
        if locality is not None and not spread:
            loc_rows = np.where(elig[None, :], locality[members], 0.0)
            cand = np.flatnonzero(loc_rows.max(axis=1) > 0.0)
            if len(cand):
                handled = np.zeros(len(members), dtype=bool)
                if active.any():
                    cap_ok_l = (cap[:, active] >= d[active]).all(axis=1)
                else:
                    cap_ok_l = np.ones(n_nodes, dtype=bool)
                pend = (outstanding.astype(np.int64).copy()
                        if outstanding is not None
                        else np.zeros(n_nodes, dtype=np.int64))
                for j in cand:
                    pref = int(np.argmax(loc_rows[j]))
                    if not cap_ok_l[pref]:
                        continue  # never feasible there: spill now
                    fits_now = (not active.any()) or bool(
                        (avail[pref, active] >= d[active]).all())
                    if fits_now:
                        out[members[j]] = pref
                        avail[pref] -= d
                        pend[pref] += 1
                        handled[j] = True
                    elif pend[pref] < spill_depth:
                        # bounded wait: stay unassigned this tick
                        # rather than pay the transfer elsewhere
                        handled[j] = True
                members = members[~handled]
                if len(members) == 0:
                    continue
        if active.any():
            with np.errstate(divide="ignore", invalid="ignore"):
                per_r = np.floor(avail[:, active] / d[active])
            fit = np.maximum(per_r.min(axis=1), 0.0)
            fit = np.where(np.isfinite(fit), fit, len(members))
            # infeasible-anywhere guard: nodes whose *capacity* can't ever
            # hold the demand contribute 0 (matches EventScheduler feasible())
            cap_ok = (cap[:, active] >= d[active]).all(axis=1)
            fit = np.where(cap_ok, fit, 0.0)
            # clip to the batch size: unbounded resources (e.g. 1e18 memory
            # capacity) would otherwise make np.repeat materialize petabytes
            fit = np.minimum(fit, len(members)).astype(np.int64)
        else:
            fit = np.full(n_nodes, len(members), dtype=np.int64)
        fit = np.where(elig, fit, 0)

        # hybrid policy: node 0 takes tasks while its load stays under the
        # threshold, then every node least-loaded-first up to its fit count.
        used = cap - avail
        with np.errstate(divide="ignore", invalid="ignore"):
            load = np.where(cap > 0, used / np.maximum(cap, 1e-9), 0.0).max(axis=1)
        if spread:
            t0 = 0
        elif active.any() and fit[0] > 0 and load[0] < threshold:
            room = np.floor((threshold * cap[0, active] - used[0, active])
                            / d[active]).min()
            t0 = int(np.clip(room, 0, fit[0]))
        elif not active.any():
            t0 = len(members) if load[0] < threshold and elig[0] else 0
        else:
            t0 = 0
        order = np.argsort(load, kind="stable")
        if spread:
            # round-robin over eligible nodes (least-loaded first): one
            # task per node per round, so members actually spread instead
            # of filling the emptiest node to its fit count
            counts_o = fit[order].astype(np.int64)
            max_r = int(counts_o.max(initial=0))
            if max_r:
                rounds = counts_o[None, :] > np.arange(max_r)[:, None]
                assignment_nodes = order.astype(np.int32)[
                    np.nonzero(rounds)[1]]
            else:
                assignment_nodes = np.zeros(0, dtype=np.int32)
        else:
            counts = [min(t0, len(members))]
            nodes_seq = [0]
            remaining_fit = fit.copy()
            remaining_fit[0] -= counts[0]
            for i in order:
                nodes_seq.append(int(i))
                counts.append(int(remaining_fit[i]))
            assignment_nodes = np.repeat(np.asarray(nodes_seq, dtype=np.int32),
                                         np.asarray(counts, dtype=np.int64))
        take = min(len(members), len(assignment_nodes))
        if take > 0:
            chosen = assignment_nodes[:take]
            out[members[:take]] = chosen
            # ufunc.at accumulates correctly over repeated node indices
            np.subtract.at(avail, chosen, d)
    return out, avail


def fire_edges_np(done_mask: np.ndarray, src: np.ndarray, dst: np.ndarray,
                  consumed: np.ndarray, indeg: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Completion wave over a static edge list (bench / bulk-admission path).

    Returns (new indeg, new consumed)."""
    fire = done_mask[src] & ~consumed
    if fire.any():
        indeg = indeg.copy()
        np.subtract.at(indeg, dst[fire], 1)
        consumed = consumed | fire
    return indeg, consumed


def pack_bundles_np(demands: np.ndarray, avail: np.ndarray, cap: np.ndarray,
                    strategy: str,
                    eligible: Optional[np.ndarray] = None
                    ) -> Optional[np.ndarray]:
    """Bin-pack one placement group's bundles onto nodes.

    The decision core of the reference's GcsPlacementGroupScheduler
    (ray: src/ray/gcs/gcs_server/gcs_placement_group_scheduler.cc) as a
    vectorized solve: demands [B,R], avail/cap [N,R]. ``eligible`` [B,N]
    restricts which nodes may host each bundle (per-NAME custom-resource
    feasibility computed by the caller). Returns node index per bundle,
    or None if no placement exists under ``avail``.

    Strategies (reference: python/ray/util/placement_group.py):
      PACK         prefer one node, spill when full
      SPREAD       prefer distinct nodes, reuse when fewer nodes
      STRICT_PACK  all bundles on ONE node or fail
      STRICT_SPREAD all bundles on DISTINCT nodes or fail
    """
    B, R = demands.shape
    N = avail.shape[0]
    alive = cap.any(axis=1)
    ok = np.broadcast_to(alive, (B, N)).copy()
    if eligible is not None:
        ok &= eligible
    rem = avail.copy()
    out = np.full(B, -1, dtype=np.int32)
    # least-loaded-first node order (deterministic tiebreak by index)
    with np.errstate(divide="ignore", invalid="ignore"):
        load = np.where(cap > 0, (cap - avail) / np.maximum(cap, 1e-9),
                        0.0).max(axis=1)
    order = np.argsort(load, kind="stable")

    if strategy == "STRICT_PACK":
        total = demands.sum(axis=0)
        all_ok = ok.all(axis=0)
        for n in order:
            if all_ok[n] and (rem[n] >= total).all():
                out[:] = n
                return out
        return None

    # big bundles first: greedy first-fit-decreasing
    bundle_order = np.argsort(-demands.sum(axis=1), kind="stable")
    if strategy == "STRICT_SPREAD":
        used = np.zeros(N, dtype=bool)
        for b in bundle_order:
            placed = False
            for n in order:
                if ok[b, n] and not used[n] \
                        and (rem[n] >= demands[b]).all():
                    out[b] = n
                    rem[n] -= demands[b]
                    used[n] = True
                    placed = True
                    break
            if not placed:
                return None
        return out

    if strategy == "SPREAD":
        used = np.zeros(N, dtype=bool)
        for b in bundle_order:
            placed = False
            for prefer_fresh in (True, False):
                for n in order:
                    if not ok[b, n] or (used[n] and prefer_fresh):
                        continue
                    if (rem[n] >= demands[b]).all():
                        out[b] = n
                        rem[n] -= demands[b]
                        used[n] = True
                        placed = True
                        break
                if placed:
                    break
            if not placed:
                return None
        return out

    # PACK (default): fill the least-loaded node, spill in node order
    for b in bundle_order:
        placed = False
        for n in order:
            if ok[b, n] and (rem[n] >= demands[b]).all():
                out[b] = n
                rem[n] -= demands[b]
                placed = True
                break
        if not placed:
            return None
    return out


def jax_pack_many(demands, avail, cap, *, strict_spread: bool):
    """Batched PG bin-pack on device: G placement groups of B bundles
    each ([G,B,R]) packed against ONE shared node state [N,R] — the
    north star's \"GCS placement-group packing ... batched bin-packing
    solve co-resident on the same chip\". Sequential consumption over
    (group, bundle) via nested scans; returns (node_of [G,B], ok [G],
    final avail). First-fit over least-index nodes (deterministic).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    G, B, R = demands.shape
    N = avail.shape[0]

    @jax.jit
    def pack(demands, avail, cap):
        alive = (cap > 0).any(axis=1)

        def per_group(carry, g):
            avail = carry

            def per_bundle(bcarry, b):
                rem, used, node_of, ok = bcarry
                d = demands[g, b]
                fits = (rem >= d[None, :]).all(axis=1) & alive
                if strict_spread:
                    fits = fits & ~used
                n = jnp.argmax(fits)  # first fitting node
                found = fits.any()
                rem = jnp.where(found,
                                rem.at[n].add(-d), rem)
                used = used.at[n].set(used[n] | found)
                node_of = node_of.at[b].set(jnp.where(found, n, -1))
                return (rem, used, node_of, ok & found), None

            (rem, _used, node_of, ok), _ = lax.scan(
                per_bundle,
                (avail, jnp.zeros(N, bool),
                 jnp.full(B, -1, jnp.int32), jnp.bool_(True)),
                jnp.arange(B))
            # 2-phase: commit the group's reservation only if every
            # bundle found a node (prepare-all-or-rollback)
            avail = jnp.where(ok, rem, avail)
            node_of = jnp.where(ok, node_of, jnp.full(B, -1, jnp.int32))
            return avail, (node_of, ok)

        avail, (node_of, ok) = lax.scan(per_group, avail, jnp.arange(G))
        return node_of, ok, avail

    return pack(demands, avail, cap)


def pack_gangs_tiered_np(demands: np.ndarray, tiers: np.ndarray,
                         avail: np.ndarray, cap: np.ndarray,
                         spread: Optional[np.ndarray] = None
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Tier-aware batched gang pack (QoS plane / gang-aware autoscaler).

    Like the sequential core of :func:`jax_pack_many` but gangs are
    admitted in strict priority-tier order — higher ``tiers[g]`` first,
    FIFO (submission index) within a tier — so a low-tier gang can never
    reserve capacity ahead of a higher-tier one that also fits.  Each
    gang of B bundles (demands [G,B,R]) is all-or-nothing against the
    shared node state [N,R]: the reservation commits only if every
    bundle found a node, otherwise the gang's trial consumption rolls
    back entirely (no partial placement is ever visible).

    ``spread`` [G] bool marks gangs whose non-empty bundles must land
    on DISTINCT nodes (STRICT_SPREAD); zero-demand padding rows are
    exempt so callers may pad ragged gang sizes freely.

    Returns (node_of [G,B] with -1 for unplaced, ok [G], final avail)
    in the ORIGINAL gang order regardless of tier permutation.
    """
    G, B, R = demands.shape
    N = avail.shape[0]
    alive = cap.any(axis=1)
    rem = avail.copy()
    node_of = np.full((G, B), -1, dtype=np.int32)
    ok = np.zeros(G, dtype=bool)
    # strict tiers with FIFO inside: stable sort on descending tier
    order = np.argsort(-np.asarray(tiers, dtype=np.int64), kind="stable")
    for g in order:
        trial = rem.copy()
        placed = np.full(B, -1, dtype=np.int32)
        used = np.zeros(N, dtype=bool)
        distinct = bool(spread[g]) if spread is not None else False
        good = True
        for b in range(B):
            d = demands[g, b]
            real = bool((d > 0).any())
            fits = alive & (trial >= d[None, :]).all(axis=1)
            if distinct and real:
                fits &= ~used
            n = int(np.argmax(fits))
            if not fits.any():
                good = False
                break
            trial[n] -= d
            if real:
                used[n] = True
            placed[b] = n
        if good:
            rem = trial
            node_of[g] = placed
            ok[g] = True
    return node_of, ok, rem


def jax_pack_many_tiered(demands, tiers, avail, cap, *,
                         strict_spread: bool):
    """Tier-aware :func:`jax_pack_many`: permute the gang axis into
    strict-tier order (higher first, FIFO within — stable argsort on
    the host, same discipline as :func:`pack_gangs_tiered_np`), run the
    batched on-device pack, then un-permute so callers see results in
    submission order. The scan itself is tier-oblivious; ordering IS
    the policy, exactly like priority drains in the tensor scheduler.
    """
    import numpy as _np

    order = _np.argsort(-_np.asarray(tiers, dtype=_np.int64),
                        kind="stable")
    inv = _np.empty_like(order)
    inv[order] = _np.arange(order.shape[0])
    node_of, ok, avail = jax_pack_many(
        _np.asarray(demands)[order], avail, cap,
        strict_spread=strict_spread)
    return _np.asarray(node_of)[inv], _np.asarray(ok)[inv], avail


# ======================================================================
# jax backend
# ======================================================================

def _assign_class_traced(members, d, avail, cap, threshold, n_nodes, batch_cap,
                         elig=None, spread=None):
    """One scheduling class: partition `members` (bool mask over a flat task
    axis) across nodes. Traced under jit; shared by the runtime assign kernel
    and the benchmark whole-graph tick. Returns (assign_mask, chosen, avail).

    elig [N] bool restricts the class to a node subset (None = all);
    spread (scalar bool) drops the local-node bias (t0 = 0) — the jitted
    approximation of SPREAD (the numpy path does true round-robin).
    """
    import jax
    import jax.numpy as jnp

    rank = jnp.cumsum(members) - 1
    active = d > 0
    safe_d = jnp.where(active, d, 1.0)
    per_r = jnp.where(active[None, :], jnp.floor(avail / safe_d), jnp.inf)
    fit = jnp.clip(per_r.min(axis=1), 0, None)
    cap_ok = jnp.where(active[None, :], cap >= d, True).all(axis=1)
    # dead (removed) nodes have all-zero capacity and must take nothing —
    # even zero-demand tasks, which would otherwise see load 0
    alive = (cap > 0).any(axis=1)
    if elig is not None:
        alive = alive & elig
    fit = jnp.where(cap_ok & alive, fit, 0.0)
    fit = jnp.minimum(fit, jnp.float32(batch_cap)).astype(jnp.int32)

    used_now = cap - avail
    load_now = jnp.where(cap > 0, used_now / jnp.maximum(cap, 1e-9),
                         0.0).max(axis=1)
    k = members.sum()
    room0 = jnp.where(active,
                      jnp.floor((threshold * cap[0] - used_now[0]) / safe_d),
                      jnp.inf).min()
    any_active = active.any()
    t0 = jnp.where(any_active,
                   jnp.clip(room0, 0, fit[0]),
                   jnp.where(load_now[0] < threshold, k, 0))
    t0 = jnp.where((fit[0] > 0) | (~any_active), t0, 0)
    t0 = jnp.where(load_now[0] < threshold, t0, 0)
    t0 = jnp.where(alive[0], t0, 0).astype(jnp.int32)
    if spread is not None:
        t0 = jnp.where(spread, 0, t0)

    order = jnp.argsort(load_now, stable=True)
    fit_rest = fit.at[0].add(-t0)
    seq_nodes = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 order.astype(jnp.int32)])
    seq_counts = jnp.concatenate([t0[None], fit_rest[order]])
    if spread is not None:
        # True round-robin parity with the numpy path under SPREAD:
        # water-fill the load-ordered nodes — every node takes
        # min(fit, t) with t the number of full round-robin rounds, and
        # the first r nodes still holding capacity take one extra
        # (r = tasks left in the final partial round). Per-node COUNTS
        # match the numpy round-robin exactly; only the task->node
        # interleaving differs (tasks of one class are interchangeable).
        fit_o = fit[order]
        k_tasks = jnp.minimum(k.astype(jnp.int32), fit_o.sum())
        lo = jnp.int32(0)
        hi = jnp.int32(batch_cap)
        for _ in range(int(batch_cap).bit_length() + 1):
            mid = (lo + hi + 1) // 2
            ok_mid = jnp.minimum(fit_o, mid).sum() <= k_tasks
            lo = jnp.where(ok_mid, mid, lo)
            hi = jnp.where(ok_mid, hi, mid - 1)
        base = jnp.minimum(fit_o, lo)
        rem = k_tasks - base.sum()
        can_more = fit_o > lo
        extra = can_more & (jnp.cumsum(can_more) <= rem)
        rr_counts = base + extra.astype(jnp.int32)
        seq_counts = jnp.where(
            spread,
            jnp.concatenate([jnp.zeros((1,), jnp.int32), rr_counts]),
            seq_counts)
    cum = jnp.cumsum(seq_counts)
    total = cum[-1]
    # Segment lookup without any [C, N] materialization: ``rank`` is
    # monotone (a cumsum), so instead of comparing every rank against
    # every boundary (compare-all: ~5 ms at C=1M) or per-element binary
    # search (jnp.searchsorted scan lowering: ~50 ms at C=1M), find each
    # boundary's position in rank (N+1 tiny binary searches), scatter unit
    # deltas, and cumsum: seg[i] = #{j : pos[j] <= i} = #{j : cum[j] <=
    # rank[i]}.
    C = members.shape[0]
    pos = jnp.searchsorted(rank, cum, side="left", method="scan")
    delta = jnp.zeros((C + 1,), jnp.int32).at[jnp.clip(pos, 0, C)].add(1)
    seg = jnp.cumsum(delta)[:C]
    seg = jnp.clip(seg, 0, n_nodes)
    chosen = seq_nodes[seg]
    assign_mask = members & (rank < total) & (rank >= 0)
    # per-node assignment counts from the same boundaries (no one-hot):
    # segment j received min(cum[j], k) - min(cum[j-1], k) tasks
    k = jnp.minimum(rank[-1] + 1, total).astype(cum.dtype)
    m = jnp.minimum(cum, k)
    seg_assigned = (m - jnp.concatenate([jnp.zeros((1,), m.dtype), m[:-1]])
                    ).astype(jnp.float32)
    per_node = jnp.zeros((n_nodes,), jnp.float32).at[seq_nodes].add(
        seg_assigned)
    avail = avail - per_node[:, None] * d[None, :]
    return assign_mask, chosen, avail, per_node


def _scan_classes(ready, cls, demands, avail, cap, threshold, n_nodes,
                  batch_cap, class_mask=None, class_spread=None):
    """Sequential capacity consumption over the class axis via lax.scan.

    Class count is DATA (the demands array's leading dim), not a Python
    unroll: one compiled program serves any class count with the same
    padded shape, so newly observed scheduling classes never trigger an
    XLA recompile (classes are padded to power-of-two buckets by callers;
    a zero-demand padding class has no members and assigns nothing).

    Returns (node_of [C] int32 with -1 = unassigned, assigned [C] bool,
    new avail, release [N,R] = total resources the assigned tasks took,
    for the instant-completion path to hand back).

    ``assigned`` is returned SEPARATELY from ``node_of`` on purpose:
    state updates must derive from the cheap mask so that when a caller
    discards node_of (the fused drive loop does), XLA can dead-code-
    eliminate the per-task ``chosen`` gather chain — deriving the mask
    from ``node_of >= 0`` instead keeps that gather live and costs ~8x
    on the 1M north star.

    Tiny class counts (the benchmark graphs, K <= 4) statically unroll —
    a scan's dynamic demand slice blocks fusion inside the drive
    while_loop. Larger counts scan (class as data: no recompile as
    classes accumulate).
    """
    import jax.numpy as jnp
    from jax import lax

    C = ready.shape[0]
    K = demands.shape[0]
    node_of0 = jnp.full((C,), -1, dtype=jnp.int32)
    assigned0 = jnp.zeros((C,), dtype=bool)
    release0 = jnp.zeros_like(avail)

    if K <= 4:
        node_of, assigned, release = node_of0, assigned0, release0
        for c in range(K):
            members = ready & (cls == c)
            assign_mask, chosen, avail, per_node = _assign_class_traced(
                members, demands[c], avail, cap, threshold, n_nodes,
                batch_cap,
                None if class_mask is None else class_mask[c],
                None if class_spread is None else class_spread[c])
            node_of = jnp.where(assign_mask, chosen, node_of)
            assigned = assigned | assign_mask
            release = release + per_node[:, None] * demands[c][None, :]
        return node_of, assigned, avail, release

    def step(carry, c):
        node_of, assigned, avail, release = carry
        members = ready & (cls == c)
        assign_mask, chosen, avail, per_node = _assign_class_traced(
            members, demands[c], avail, cap, threshold, n_nodes, batch_cap,
            None if class_mask is None else class_mask[c],
            None if class_spread is None else class_spread[c])
        node_of = jnp.where(assign_mask, chosen, node_of)
        assigned = assigned | assign_mask
        release = release + per_node[:, None] * demands[c][None, :]
        return (node_of, assigned, avail, release), None

    (node_of, assigned, avail, release), _ = lax.scan(
        step, (node_of0, assigned0, avail, release0),
        jnp.arange(K, dtype=jnp.int32))
    return node_of, assigned, avail, release


def _make_drive_loop(tick, cls, pin, demands, cap, src, dst, max_ticks):
    """while_loop driving the instant tick to DAG completion (shared by
    _jit_drive and _jit_bench so the loop cannot diverge between them)."""
    import jax.numpy as jnp
    from jax import lax

    def drive(state, indeg, avail, consumed):
        def cond(carry):
            state, indeg, avail, consumed, ticks = carry
            return (state == WAITING).any() & (ticks < max_ticks)

        def body(carry):
            state, indeg, avail, consumed, ticks = carry
            state, indeg, avail, _node_of, consumed = tick(
                state, indeg, cls, pin, demands, avail, cap, src, dst,
                consumed)
            return (state, indeg, avail, consumed, ticks + 1)

        return lax.while_loop(
            cond, body, (state, indeg, avail, consumed, jnp.int32(0)))

    return drive


@functools.lru_cache(maxsize=None)
def _jit_assign(threshold: float):
    """Jitted assignment over a compacted ready batch (runtime big-batch
    path). Inputs: ready_cls [Kpad] int32 (class per ready task), valid
    [Kpad] bool, demands [K,R], avail/cap [N,R]. Returns (node_of [Kpad]
    int32, -1 = not assigned; new avail). jit specializes on the padded
    shapes; the class axis is scanned, so class count only recompiles at
    power-of-two bucket boundaries (the padding done by jax_assign)."""
    import jax

    def assign(ready_cls, valid, demands, avail, cap, class_mask,
               class_spread):
        kpad = ready_cls.shape[0]
        node_of, _assigned, avail, _release = _scan_classes(
            valid, ready_cls, demands, avail, cap, threshold,
            avail.shape[0], kpad, class_mask, class_spread)
        return node_of, avail

    return jax.jit(assign)


def jax_assign(ready_cls: np.ndarray, demands: np.ndarray, avail: np.ndarray,
               cap: np.ndarray, threshold: float,
               class_mask: Optional[np.ndarray] = None,
               class_spread: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Pad the ready batch AND the class axis to power-of-two buckets
    (bounds recompiles to O(log) in both) and run the jitted assignment.
    Same contract as assign_np given ready_cls = cls[ready_idx]."""
    k = len(ready_cls)
    kpad = 1 << max(9, (k - 1).bit_length())
    padded = np.zeros(kpad, dtype=np.int32)
    padded[:k] = ready_cls
    valid = np.zeros(kpad, dtype=bool)
    valid[:k] = True
    num_classes = int(demands.shape[0])
    n_nodes = avail.shape[0]
    kcls = 1 << max(0, (num_classes - 1).bit_length())
    demands = demands.astype(np.float32)
    if class_mask is None:
        class_mask = np.ones((num_classes, n_nodes), dtype=bool)
    if class_spread is None:
        class_spread = np.zeros(num_classes, dtype=bool)
    if kcls > num_classes:
        pad_k = kcls - num_classes
        demands = np.concatenate(
            [demands, np.zeros((pad_k, demands.shape[1]),
                               dtype=np.float32)], axis=0)
        class_mask = np.concatenate(
            [class_mask, np.zeros((pad_k, n_nodes), dtype=bool)], axis=0)
        class_spread = np.concatenate(
            [class_spread, np.zeros(pad_k, dtype=bool)])
    fn = _jit_assign(float(threshold))
    node_of, new_avail = fn(padded, valid, demands,
                            avail.astype(np.float32), cap.astype(np.float32),
                            class_mask.astype(bool),
                            class_spread.astype(bool))
    return np.asarray(node_of)[:k], np.asarray(new_avail)


def _make_instant_tick(threshold: float):
    """Traced instant-completion tick body shared by the single-tick entry
    point and the fused on-device drive loop: ready-set -> assignment ->
    instant completion -> resource release -> edge firing.

    ``pin[t] >= 0`` assigns task t straight to that node with no capacity
    partition — the batched analog of the reference's actor-call path,
    where calls go directly to the actor's leased worker and never touch
    the scheduler (ray: src/ray/core_worker/transport/ —
    ActorTaskSubmitter submits over the actor's own queue). Pinned tasks
    should use an all-zero demand class: the actor's resources were
    acquired once at creation, not per call.
    """
    import jax
    import jax.numpy as jnp

    def tick(state, indeg, cls, pin, demands, avail, cap, src, dst, consumed):
        C = state.shape[0]
        ready = (state == WAITING) & (indeg <= 0)
        pinned = ready & (pin >= 0)
        node_of = jnp.where(pinned, pin, jnp.int32(-1))
        state = jnp.where(pinned, jnp.int8(RUNNING), state)
        ready = ready & ~pinned
        nof, assigned, avail, release = _scan_classes(
            ready, cls, demands, avail, cap, threshold, avail.shape[0], C)
        node_of = jnp.where(assigned, nof, node_of)
        state = jnp.where(assigned, jnp.int8(RUNNING), state)

        newly_done = state == RUNNING
        # instant completion releases exactly what assignment just took
        # (pinned tasks use zero-demand classes), so reuse the scan's
        # accumulated release matrix instead of recounting the task axis
        avail = jnp.minimum(avail + release, cap)
        state = jnp.where(newly_done, jnp.int8(DONE), state)
        done = state == DONE
        fire = done[src] & ~consumed
        # builders emit dst sorted ascending -> no sort inside segment_sum
        dec = jax.ops.segment_sum(fire.astype(jnp.int32), dst,
                                  num_segments=C, indices_are_sorted=True)
        indeg = indeg - dec
        consumed = consumed | fire
        return state, indeg, avail, node_of, consumed

    return tick


@functools.lru_cache(maxsize=None)
def _jit_drive(threshold: float, max_ticks: int, donate: bool = True):
    """Whole-DAG drive fused into ONE device program: lax.while_loop over
    the instant-completion tick. One dispatch + one host sync for the
    entire graph — this is the north-star measurement path (per-tick host
    round-trips would otherwise be part of what is timed)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    tick = _make_instant_tick(threshold)

    def drive(state, indeg, cls, pin, demands, avail, cap, src, dst,
              consumed):
        loop = _make_drive_loop(tick, cls, pin, demands, cap, src, dst,
                                max_ticks)
        return loop(state, indeg, avail, consumed)

    return jax.jit(drive, donate_argnums=(0, 1, 9) if donate else ())


def jax_drive(state, indeg, cls, pin, demands, avail, cap, src, dst,
              consumed, *, num_classes: int, threshold: float,
              max_ticks: int, donate: bool = True):
    """Run the fused on-device DAG drive; returns (state, ..., ticks).

    CONTRACT: ``dst`` must be sorted ascending (the completion wave uses
    segment_sum(indices_are_sorted=True); unsorted dst silently corrupts
    indegrees). benchmarks._device_state enforces this by sorting.

    donate=False keeps the input buffers alive so the same device state
    can be re-driven (benchmark repeats without re-transferring)."""
    del num_classes  # class count is now the demands array's leading dim
    fn = _jit_drive(float(threshold), int(max_ticks), bool(donate))
    return fn(state, indeg, cls, pin, demands, avail, cap, src, dst,
              consumed)


@functools.lru_cache(maxsize=None)
def _jit_bench(threshold: float, max_ticks: int, k_reps: int):
    """K whole-DAG drives chained by true data dependence, in ONE program.

    Benchmark measurement core. Each repetition re-initializes the graph
    state from the originals PLUS an all-zero value computed from the
    previous repetition's outputs (``prev_state == RUNNING`` is always
    false after a completed drive, but XLA cannot prove that), so the
    compiler can neither CSE the repetitions nor hoist them out of the
    loop, and the executions serialize. Fetching the returned tick-count
    scalar ends the timed region: all K drives are complete when it
    arrives. Cost model: T(K) = dispatch_and_fetch + K * drive_time;
    run at two K values and difference to cancel the fixed part.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    tick = _make_instant_tick(threshold)

    def bench(state0, indeg0, cls, pin, demands, avail0, cap, src, dst,
              consumed0):
        drive = _make_drive_loop(tick, cls, pin, demands, cap, src, dst,
                                 max_ticks)

        def outer(i, carry):
            prev_state, _pi, _pa, _pc, total = carry
            opaque = (prev_state == RUNNING).astype(jnp.int32)  # all zeros
            state = (jnp.full_like(prev_state, WAITING)
                     + opaque.astype(jnp.int8))
            indeg = indeg0 + opaque
            avail = avail0 + _pa * 0.0  # original avail + opaque zero
            consumed = consumed0 | (prev_state == RUNNING)[src]
            state, indeg, avail, consumed, t = drive(
                state, indeg, avail, consumed)
            return (state, indeg, avail, consumed, total + t)

        state, indeg, avail, consumed, total = lax.fori_loop(
            0, k_reps, outer,
            (state0, indeg0, avail0, consumed0, jnp.int32(0)))
        return total, state

    return jax.jit(bench)


def jax_bench(state, indeg, cls, pin, demands, avail, cap, src, dst,
              consumed, *, num_classes: int, threshold: float,
              max_ticks: int, k_reps: int):
    """Run K chained drives; returns (total_ticks scalar, final state).

    CONTRACT: ``dst`` must be sorted ascending (see jax_drive)."""
    del num_classes  # class count is now the demands array's leading dim
    fn = _jit_bench(float(threshold), int(max_ticks), int(k_reps))
    return fn(state, indeg, cls, pin, demands, avail, cap, src, dst,
              consumed)


@functools.lru_cache(maxsize=None)
def _jit_tick(threshold: float, instant_completion: bool):
    """Build a jitted whole-graph tick: ready-set -> per-class assignment
    -> (optionally) instant completion + edge firing.

    ``instant_completion=True`` is the benchmark/simulation mode: assigned
    tasks complete within the tick and their out-edges fire, so one tick
    advances one wave of the DAG. The runtime scheduler uses
    ``instant_completion=False`` and reports completions from real
    executions between ticks.
    """
    import jax
    import jax.numpy as jnp

    if instant_completion:
        tick = _make_instant_tick(threshold)
        return jax.jit(tick, donate_argnums=(0, 1, 9))

    def tick(state, indeg, cls, pin, demands, avail, cap, src, dst, consumed):
        C = state.shape[0]
        ready = (state == WAITING) & (indeg <= 0)
        pinned = ready & (pin >= 0)
        node_of = jnp.where(pinned, pin, jnp.int32(-1))
        state = jnp.where(pinned, jnp.int8(RUNNING), state)
        ready = ready & ~pinned
        nof, assigned, avail, _release = _scan_classes(
            ready, cls, demands, avail, cap, threshold, avail.shape[0], C)
        node_of = jnp.where(assigned, nof, node_of)
        state = jnp.where(assigned, jnp.int8(RUNNING), state)
        return state, indeg, avail, node_of, consumed

    return jax.jit(tick, donate_argnums=(0, 1, 9))


def jax_tick(state, indeg, cls, pin, demands, avail, cap, src, dst, consumed,
             *, num_classes: int, threshold: float,
             instant_completion: bool = False):
    """Run one jitted tick; shapes are static per (C, E, N, R, K) bucket.

    CONTRACT: ``dst`` must be sorted ascending (see jax_drive)."""
    del num_classes  # class count is now the demands array's leading dim
    fn = _jit_tick(float(threshold), bool(instant_completion))
    return fn(state, indeg, cls, pin, demands, avail, cap, src, dst, consumed)
