"""Device-side multilevel coarsening: clustering + contraction in jax.

This is the other half of ``partition(engine="device")`` (DESIGN.md §6).
``refine_device.py`` moved per-level refinement onto the device in PR 6 but
the V-cycle's *descend* — heavy-connectivity clustering and hypergraph
contraction — stayed host scipy and came to dominate the device profile.
This module keeps the whole descend on device: one jitted *clustering*
kernel proposes and grants weight-capped merges, and one jitted
*contraction* kernel rebuilds the coarse level's padded CSR arrays, so the
only per-level host traffic is two scalars (surviving vertex / pin counts,
needed to pick the next level's static shape buckets).

Design constraints are the same as the refinement kernel, plus one: XLA's
CPU backend has no fast scatter *or* argsort, so the usual "sort pins by
cluster id, unique, rebuild" contraction is out.  What works (measured):
cumsum ~0.6 ms and gathers ~0.1 ms per 112k pins, one value-only sort
~6 ms, one scatter ~5 ms.  The kernels are built around that budget:

- **Leader-based clustering, no similarity matrix.**  Each round every
  live cluster representative draws two incident nets (counter-based hash,
  no RNG state) and keeps the better score ``c(n)/(|n|-1)`` — the exact
  per-net term of the host's heavy-connectivity similarity; a
  two-choice sample replaces the row argmax.  The net's *anchor* (its
  first pin's vertex) is the merge target.  A per-round role hash splits
  vertices into proposers and acceptors, so merges are one-sided and
  deterministic; an anchor only accepts while it is itself an unabsorbed
  acceptor, which keeps cluster weights exact.
- **Weight-capped grants via segmented prefix sums.**  Proposals toward a
  net are granted in pin order while the anchor's running cluster weight
  stays under the cap: an inclusive prefix over the net-CSR gives each
  proposal's committed weight, a second prefix over the anchor's
  vertex-CSR orders its *nets*, and the statically-known inverse pin
  permutation transports the per-net budget back to pin slots.  No
  scatters, no sorts, exact in pin order — the device analogue of the
  host's sorted greedy grant loop.
- **Labels stay in the fine index space** during the rounds (pointer
  jumping resolves chains at the end), and contraction re-ranks the
  surviving representatives by a prefix sum.  Nets whose pins collapse
  into one cluster are *dead*: their pins are dropped and their cost
  zeroed (the device analogue of the host ``_coarsen`` singleton filter).
  Nets only ever shrink, so the finest level's big-net filter
  (``MAX_DEVICE_NET``, applied in ``_pad_level``) holds at every level.
- **Within-net duplicate pins are dropped, and contraction is
  scatter-free.**  The clustering kernel ends with one packed value sort
  (``coarse_pin * pin_bucket + slot``): surviving pins ordered by coarse
  vertex then slot, which makes same-net duplicates (two fine pins of one
  net landing in one cluster) adjacent, so a roll-compare mask removes
  them.  That dedup is what actually shrinks the pin count — and its
  shape bucket — down the hierarchy; without it ER-style instances keep
  finest-sized pin arrays at every level and the resident V-cycle loses
  to the host.  Contraction then compacts the sorted stream with
  cumsum-searchsorted selects (the vertex view falls out directly), pays
  one more pin-sized packed sort (``slot * vertex_bucket + coarse``) for
  the net view, and recovers both pin permutations by searchsorted into
  the streams — no scatter at all.  Exact coarse cluster weights come
  from a vertex-sized packed sort (duplicate coarse pins make the
  in-round running weights conservative, never under).

Compile-once bucketing, the LRU kernel cache and ``trace_count()`` follow
``refine_device.py`` exactly; zero retraces across same-bucket partitions.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hypergraph import Hypergraph
from repro.core import refine_device as _rd
from repro.core.refine_device import _hash_u32

__all__ = [
    "CLUSTER_ROUNDS",
    "MAX_LEVELS",
    "DeviceLevel",
    "finest_level",
    "coarsen_level",
    "trace_count",
]

CLUSTER_ROUNDS = 5  # merge rounds per level (one jitted call)
MAX_LEVELS = 12  # hard stop on V-cycle depth
STALL_FRACTION = 0.8  # stop descending when a level keeps >= this many vertices
_INT31 = 1 << 31  # int32 packing bound for the vertex-CSR sort key


def _bucket_fine(x: int) -> int:
    """Coarse-level shape bucket: ceil to a 512 multiple instead of the
    finest level's ×1.5 geometric ladder.  Coarse shapes are deterministic
    per (instance, seed), so repeated partitions of the same hypergraph
    still hit the kernel caches — the wide ladder's cross-size reuse buys
    nothing below the finest level, while its padding (up to 50%) inflates
    the pin- and vertex-sized ops that dominate V-cycle wall time.  The
    quantum keeps waste under 1% at realistic coarse sizes and still caps
    the number of distinct compiled shapes per instance family."""
    return max(_rd._BUCKET_MIN, -(-x // 512) * 512)

# -- retrace accounting (same contract as refine_device.py) ------------------
_TRACE_COUNT = 0


def trace_count() -> int:
    """Number of times a coarsening kernel body has been traced.  Stable
    across repeated same-bucket partitions — the compile-once test hook."""
    return _TRACE_COUNT


def _mark_trace() -> None:
    global _TRACE_COUNT
    _TRACE_COUNT += 1


@dataclass
class DeviceLevel:
    """One V-cycle level resident on device: the 13-array padded layout of
    ``refine_device._pad_level`` (consumable by ``refine_args`` directly)
    plus the inverse pin permutation the clustering kernel needs."""

    nb: int  # vertex bucket (includes 1 phantom vertex)
    mb: int  # net bucket (kept constant down the hierarchy; dead nets empty)
    pb: int  # pin bucket
    n_vertices: int  # live vertices (unpadded)
    args: tuple  # (pin_nets, net_pins, cost, w, vptr, vnets, vperm,
    #              hi, lo, lz, vhi, vlo, vlz)
    vinv: object  # (pb,) vertex-order position of each net-order pin slot


def finest_level(hg: Hypergraph) -> DeviceLevel:
    """Wrap the (cached) finest padded view as the root device level.

    Padded with the tight quantizer, not the refiner's ×1.5 ladder: the
    finest level hosts the single most expensive kernels of the whole
    V-cycle (first cluster + contract), and at realistic sizes the ladder
    wastes 30–50% of every pin- and vertex-sized op there."""
    pl = _rd._pad_level(hg, bucket=_bucket_fine)
    return DeviceLevel(
        nb=pl.nb,
        mb=pl.mb,
        pb=pl.pb,
        n_vertices=hg.n_vertices,
        args=pl.args,
        vinv=pl.vinv,
    )


# -- clustering kernel --------------------------------------------------------
def _make_clusterer(nb: int, mb: int, pb: int, rounds: int):
    def _cluster(pin_nets, net_pins, cost, w, vptr, vnets, vperm, hi, lo,
                 lo_zero, vhi, vlo, vlo_zero, vinv, n_real, cap, salt):
        _mark_trace()  # Python body: executes at trace time only
        iota = jnp.arange(nb, dtype=jnp.int32)
        vids = jnp.arange(nb, dtype=jnp.uint32)
        vdeg = (vptr[1:] - vptr[:-1]).astype(jnp.uint32)
        net_lo = jnp.where(lo_zero, 0, lo + 1)  # per-net first pin slot
        ndeg = hi + 1 - net_lo
        alive = iota < n_real
        anchor = net_pins[net_lo]  # (mb,) each net's merge target vertex
        # the exact per-net term of the host similarity: c(n) / (|n| - 1)
        nscore = jnp.where(
            ndeg >= 2,
            cost / jnp.maximum(ndeg.astype(jnp.float32) - 1.0, 1.0),
            -1.0,
        )
        owner = net_pins[vperm]  # (pb,) vertex owning each vertex-CSR position
        is_lead = vperm == net_lo[vnets]  # j anchors net vnets[j]

        def body(r, carry):
            labels, cw = carry
            ri = jnp.uint32(r)
            root = labels == iota
            prop_role = (
                _hash_u32(vids, salt ^ (ri * jnp.uint32(0x9E3779B9))) & 1
            ) == 1
            # a net is open iff its anchor is a live, unabsorbed acceptor —
            # only then does "grant toward the anchor" have exact weights
            can_accept = alive & root & ~prop_role
            open_net = can_accept[anchor] & (ndeg >= 2)
            # proposers: two-choice sample among incident nets by score
            h1 = _hash_u32(vids, salt ^ (ri * jnp.uint32(0x85EBCA77)))
            h2 = _hash_u32(h1, salt ^ jnp.uint32(0xC2B2AE35))
            safe_deg = jnp.maximum(vdeg, 1)
            i1 = vptr[:nb] + (h1 % safe_deg).astype(jnp.int32)
            i2 = vptr[:nb] + (h2 % safe_deg).astype(jnp.int32)
            e1 = vnets[i1]
            e2 = vnets[i2]
            s1 = jnp.where(open_net[e1] & (anchor[e1] != iota), nscore[e1], -1.0)
            s2 = jnp.where(open_net[e2] & (anchor[e2] != iota), nscore[e2], -1.0)
            use2 = s2 > s1
            e = jnp.where(use2, e2, e1)
            jslot = vperm[jnp.where(use2, i2, i1)]  # v's own pin slot in e
            propose = (
                alive & root & prop_role & (vdeg > 0) & (jnp.maximum(s1, s2) > 0)
            )
            # net-side: each proposal rides its own pin; inclusive prefix =
            # weight committed up to and including it, in pin order
            via = propose[net_pins] & (e[net_pins] == pin_nets)
            wprop = jnp.where(via, cw[net_pins], 0.0)
            csn = jnp.cumsum(wprop)
            base = jnp.where(lo_zero, 0.0, csn[lo])
            tot = csn[hi] - base
            # anchor-side: an acceptor grants its nets in CSR order; the
            # budget already committed before net vnets[j] is its own weight
            # plus the totals of its earlier nets
            led_t = jnp.where(is_lead, tot[vnets], 0.0)
            csl = jnp.cumsum(led_t)
            base_v = jnp.where(vlo_zero[owner], 0.0, csl[vlo[owner]])
            start_v = cw[owner] + (csl - led_t) - base_v
            start_net = start_v[vinv][net_lo]  # transported to the net axis
            # the grant cutoff is monotone in csn, so granted pins are a
            # prefix of each net's via pins: one searchsorted per net replaces
            # two more pin-sized cumsums, and a proposer reads its own grant
            # decision straight off its pin slot (each vertex pins a net at
            # most once — duplicates are deduped between levels)
            cut = jnp.minimum(
                jnp.searchsorted(
                    csn, cap - start_net + base, side="right"
                ).astype(jnp.int32)
                - 1,
                hi,
            )
            g_raw = jnp.where(cut >= 0, csn[jnp.maximum(cut, 0)], 0.0)
            g_net = jnp.maximum(g_raw - base, 0.0)
            got = propose & (start_net[e] + (csn[jslot] - base[e]) <= cap)
            # anchors absorb the granted inflow
            led_g = jnp.where(is_lead, g_net[vnets], 0.0)
            csgl = jnp.cumsum(led_g)
            inflow = csgl[vhi] - jnp.where(vlo_zero, 0.0, csgl[vlo])
            return jnp.where(got, anchor[e], labels), cw + inflow

        labels, cw = jax.lax.fori_loop(
            0, rounds, body, (iota, w.astype(jnp.float32))
        )
        # chains grow by at most one link per round; jump to the roots
        for _ in range(max(2, int(rounds).bit_length())):
            labels = labels[labels]
        root = (labels == iota) & alive
        rank = jnp.cumsum(root.astype(jnp.int32)) - 1  # root -> coarse id
        n_alive = jnp.sum(root.astype(jnp.int32))
        coarse_pin = rank[labels][net_pins]  # (pb,) coarse pin ids
        # dead nets: every pin in one cluster (covers singleton and phantom
        # nets) — the device analogue of the host singleton filter
        diff = (coarse_pin != coarse_pin[net_lo][pin_nets]).astype(jnp.int32)
        csd = jnp.cumsum(diff)
        dead = (csd[hi] - jnp.where(lo_zero, 0, csd[lo])) == 0
        keep = ~dead[pin_nets]
        # the level's one packed sort orders surviving pins by
        # (coarse vertex, slot); within a group slots ascend, so pins of the
        # same net are adjacent and duplicates (two fine pins of one net
        # falling into one cluster) drop with an adjacent-equality mask —
        # this is what actually shrinks the pin count (and its bucket) down
        # the hierarchy.  Dropped/pad entries sort to the tail as INT32_MAX.
        slot = jnp.arange(pb, dtype=jnp.int32)
        sk = jnp.sort(
            jnp.where(keep, coarse_pin * pb + slot, jnp.int32(_INT31 - 1))
        )
        valid = sk != _INT31 - 1
        scp = sk // pb
        snet = pin_nets[sk % pb]
        dup = (
            valid
            & (jnp.arange(pb) > 0)
            & (scp == jnp.roll(scp, 1))
            & (snet == jnp.roll(snet, 1))
        )
        surv = valid & ~dup
        n_pins2 = jnp.sum(surv.astype(jnp.int32))
        return labels, rank, dead, sk, surv, n_alive, n_pins2

    return jax.jit(_cluster)


# -- contraction kernel -------------------------------------------------------
def _make_contractor(nb: int, mb: int, pb: int, nbb: int, pbb: int):
    def _contract(pin_nets, cost, w, labels, rank, dead, sk, surv,
                  n_real, n_pins2):
        _mark_trace()
        dd = jnp.arange(pbb, dtype=jnp.int32)
        # order-preserving select of the surviving sorted stream (prefix sum
        # + searchsorted): position j is already coarse-vertex order
        css = jnp.cumsum(surv.astype(jnp.int32))
        srcp = jnp.searchsorted(css, dd + 1, side="left").astype(jnp.int32)
        validj = dd < n_pins2
        skj = sk[jnp.where(validj, srcp, pb - 1)]
        sortv = jnp.where(validj, skj // pb, nbb - 1).astype(jnp.int32)
        oldslot = jnp.where(validj, skj % pb, pb - 1).astype(jnp.int32)
        vnets2 = jnp.where(validj, pin_nets[oldslot], mb - 1).astype(jnp.int32)
        vedges = jnp.searchsorted(
            sortv, jnp.arange(nbb + 1, dtype=jnp.int32), side="left"
        )
        vptr2 = vedges.astype(jnp.int32)
        vl, vr = vedges[:-1], vedges[1:]
        vempty = vl == vr
        vhi2 = jnp.where(vempty, pbb - 1, vr - 1).astype(jnp.int32)
        vlo2 = jnp.where(vempty, pbb - 1, vl - 1).astype(jnp.int32)
        vlz2 = jnp.where(vempty, False, vl == 0)
        # net view: the second pin-sized packed sort restores slot order
        # (slots unique -> nets ascend again), carrying the coarse id along
        key3 = jnp.where(
            validj, oldslot * nbb + sortv, jnp.int32(_INT31 - 1)
        )
        sk3 = jnp.sort(key3)
        validd = dd < n_pins2
        oslot = jnp.where(validd, sk3 // nbb, pb - 1).astype(jnp.int32)
        np2 = jnp.where(validd, sk3 % nbb, nbb - 1).astype(jnp.int32)
        pn2 = jnp.where(validd, pin_nets[oslot], mb - 1).astype(jnp.int32)
        edges = jnp.searchsorted(
            pn2, jnp.arange(mb + 1, dtype=jnp.int32), side="left"
        )
        left, right = edges[:-1], edges[1:]
        empty = left == right
        hi2 = jnp.where(empty, pbb - 1, right - 1).astype(jnp.int32)
        lo2 = jnp.where(empty, pbb - 1, left - 1).astype(jnp.int32)
        lz2 = jnp.where(empty, False, left == 0)
        cost2 = jnp.where(dead, 0.0, cost).astype(jnp.float32)
        # both permutations fall out of searchsorted into the two ascending
        # streams (slots are unique, so each query hits its own entry)
        vperm2 = jnp.clip(
            jnp.searchsorted(oslot, oldslot, side="left"), 0, pbb - 1
        ).astype(jnp.int32)
        selkey = jnp.where(validj, sortv * pb + oldslot, jnp.int32(_INT31 - 1))
        vinv2 = jnp.clip(
            jnp.searchsorted(selkey, np2 * pb + oslot, side="left"),
            0,
            pbb - 1,
        ).astype(jnp.int32)
        # exact coarse weights: group fine vertices by coarse id with a
        # vertex-sized packed sort (in-round cw is conservative, not exact,
        # when coarse nets carry duplicate pins); the driver guarantees
        # nbb * nb fits int32 (x64 stays off)
        iota = jnp.arange(nb, dtype=jnp.int32)
        cmap = jnp.where(iota < n_real, rank[labels], nbb - 1)
        kv = cmap * nb + iota
        skv = jnp.sort(kv)
        csw = jnp.cumsum(w[skv % nb])
        scv = skv // nb
        wedges = jnp.searchsorted(
            scv, jnp.arange(nbb + 1, dtype=jnp.int32), side="left"
        )
        wl, wr = wedges[:-1], wedges[1:]
        seg = jnp.where(
            wr > wl,
            csw[jnp.maximum(wr - 1, 0)] - jnp.where(wl > 0, csw[wl - 1], 0.0),
            0.0,
        )
        w2 = jnp.where(
            jnp.arange(nbb, dtype=jnp.int32) == nbb - 1, 0.0, seg
        ).astype(jnp.float32)
        return (pn2, np2, cost2, w2, vptr2, vnets2, vperm2, hi2, lo2, lz2,
                vhi2, vlo2, vlz2, vinv2, cmap)

    return jax.jit(_contract)


_CLUSTERERS: OrderedDict[tuple, object] = OrderedDict()
_CONTRACTORS: OrderedDict[tuple, object] = OrderedDict()


def _get_cached(cache: OrderedDict, key: tuple, make):
    fn = cache.get(key)
    if fn is None:
        fn = make()
        cache[key] = fn
        while len(cache) > _rd.CACHE_SIZE:
            cache.popitem(last=False)
    else:
        cache.move_to_end(key)
    return fn


# -- public entry point -------------------------------------------------------
def coarsen_level(
    level: DeviceLevel, cluster_cap: float, seed: int, index: int
) -> tuple[DeviceLevel, object, int] | None:
    """Coarsen one level on device.  Returns ``(coarse_level, cmap,
    n_coarse)`` where ``cmap`` is a device ``(nb,)`` map from this level's
    padded vertex ids to the coarse level's (so ``batch[:, cmap]`` is the
    uncoarsening expansion), or ``None`` when clustering stalled or the
    coarse shapes would overflow the int32 sort-key packing — the driver
    then stops descending (or falls back to host coarsening entirely)."""
    nb, mb, pb = level.nb, level.mb, level.pb
    if nb * pb >= _INT31 - 1:  # the clustering tail's packed sort key
        return None
    fn = _get_cached(
        _CLUSTERERS,
        (nb, mb, pb, CLUSTER_ROUNDS),
        lambda: _make_clusterer(nb, mb, pb, CLUSTER_ROUNDS),
    )
    salt = np.uint32(
        ((seed * 0x9E3779B9) ^ ((index + 1) * 0x85EBCA77)) & 0xFFFFFFFF
    )
    labels, rank, dead, sk, surv, n_alive, n_pins2 = fn(
        *level.args,
        level.vinv,
        jnp.int32(level.n_vertices),
        jnp.float32(cluster_cap),
        salt,
    )
    n_alive = int(n_alive)
    n_pins2 = int(n_pins2)
    if n_alive >= level.n_vertices * STALL_FRACTION:
        return None
    nbb = _bucket_fine(n_alive + 1)
    pbb = _bucket_fine(max(n_pins2, 1))
    if nbb * pb >= _INT31 - 1 or nbb * nb >= _INT31:
        return None
    cfn = _get_cached(
        _CONTRACTORS,
        (nb, mb, pb, nbb, pbb),
        lambda: _make_contractor(nb, mb, pb, nbb, pbb),
    )
    out = cfn(
        level.args[0],
        level.args[2],
        level.args[3],
        labels,
        rank,
        dead,
        sk,
        surv,
        jnp.int32(level.n_vertices),
        jnp.int32(n_pins2),
    )
    args2, vinv2, cmap = tuple(out[:13]), out[13], out[14]
    coarse = DeviceLevel(
        nb=nbb, mb=mb, pb=pbb, n_vertices=n_alive, args=args2, vinv=vinv2
    )
    return coarse, cmap, n_alive
