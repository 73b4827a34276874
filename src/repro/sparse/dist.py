"""Distributed HITS/ranking sweeps under shard_map.

Edge-sharding strategies with different collective costs per sweep
(per-device bytes, vector length N, S shards):

* ``replicated``   — edges round-robin sharded; both half-steps end in a
                     full-vector psum (all-reduce). Cost ≈ 4N (2 all-reduce,
                     all-reduce moves ~2 bytes/byte).
* ``dual_blocked`` — two edge partitions (by dst block for the authority
                     step, by src block for the hub step); both half-steps
                     scatter only into the owner's block, combine = 2
                     all-gathers. Cost ≈ 2N.

The §Perf hillclimb for the ranking workload walks exactly this ladder.
All variants compute the same fixed point (tests assert vs the
single-device sweep).
"""
from __future__ import annotations

import re
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..graph.partition import partition_edges, partition_edges_by_dst_block
from ..graph.structure import Graph, next_pow2


def _seg_sum(x_g, idx, n):
    return jax.ops.segment_sum(x_g, idx, num_segments=n)


def _mul(v, c):
    """v: (N,) or (N, V); c: None or (N,) — broadcast c over V."""
    if c is None:
        return v
    return v * (c[:, None] if v.ndim == 2 else c)


def build_edge_shards(g: Graph, n_shards: int, mode: str = "replicated"):
    """Host-side partition. Returns dict of (S, E_loc) arrays (+ metadata)."""
    if mode == "replicated":
        parts = partition_edges(g, n_shards)
        parts["mode"] = "replicated"
        return parts
    if mode == "dual_blocked":
        a_part = partition_edges_by_dst_block(g, n_shards)
        h_part = partition_edges_by_dst_block(g.reverse(), n_shards)
        # reverse() swaps src/dst: h_part's "dst" is the original src, so the
        # hub step scatters block-locally.
        return {"mode": "dual_blocked", "a": a_part, "h": h_part,
                "n_block": a_part["n_block"]}
    if mode == "dual_blocked_compact":
        # hub vectors live in the reordered non-dangling space (dangling
        # pages have zero hub score — never ship them; paper-reordering
        # fused into the distributed layout, §Perf C3)
        dang = g.dangling_mask()
        nd_ids = np.nonzero(~dang)[0].astype(np.int32)
        remap = np.full(g.n_nodes, -1, np.int32)
        remap[nd_ids] = np.arange(len(nd_ids), dtype=np.int32)
        src_c = remap[g.src]
        assert (src_c >= 0).all()
        a_part = partition_edges_by_dst_block(
            Graph(g.n_nodes, src_c, g.dst), n_shards)  # src in compact space
        h_part = partition_edges_by_dst_block(
            Graph(len(nd_ids), g.dst, src_c), n_shards)  # blocked by src_c
        return {"mode": "dual_blocked_compact", "a": a_part, "h": h_part,
                "n_block": a_part["n_block"], "nb_h": h_part["n_block"],
                "nd_ids": nd_ids, "n_hub": len(nd_ids)}
    raise ValueError(mode)


def _flat_axis_index(axes):
    """Flattened shard index across possibly-multiple mesh axes."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def make_dist_hits_sweep(mesh, shards, n: int, axes=("data",),
                         ca: Optional[np.ndarray] = None,
                         ch: Optional[np.ndarray] = None,
                         dtype=jnp.float32):
    """Return (sweep_fn, h0, device_args) for the given strategy.

    sweep_fn(h, *device_args) -> (h_next_normalized, a); call under jit with
    the mesh active. ``h`` layout depends on the mode (full vs blocked).
    """
    mode = shards["mode"]
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    ax = axes if len(axes) > 1 else axes[0]
    espec = P(ax, None)

    ca_j = None if ca is None else jnp.asarray(ca, dtype)
    ch_j = None if ch is None else jnp.asarray(ch, dtype)

    if mode == "replicated":

        def sweep(h, src, dst, w, mask):
            wm = w[0] * mask[0]
            a_p = _seg_sum(_mul(jnp.take(_mul(h, ch_j), src[0], axis=0),
                                None) * (wm[:, None] if h.ndim == 2 else wm),
                           dst[0], n)
            a = jax.lax.psum(a_p, ax)
            h_p = _seg_sum(jnp.take(_mul(a, ca_j), dst[0], axis=0)
                           * (wm[:, None] if h.ndim == 2 else wm),
                           src[0], n)
            h_new = jax.lax.psum(h_p, ax)
            h_new = h_new / (jnp.sum(jnp.abs(h_new), axis=0,
                                     keepdims=h.ndim > 1) + 1e-30)
            return h_new, a

        smapped = jax.shard_map(
            sweep, mesh=mesh,
            in_specs=(P(), espec, espec, espec, espec),
            out_specs=(P(), P()),
        )
        args = tuple(jnp.asarray(shards[k]) for k in ("src", "dst", "w", "mask"))
        h0 = jnp.full((n,), 1.0 / n, dtype)
        return smapped, h0, args

    if mode == "dual_blocked_compact":
        nb_a = int(shards["n_block"])
        nb_h = int(shards["nb_h"])
        n_hub = int(shards["n_hub"])
        a_p, h_p = shards["a"], shards["h"]
        ch_c = None if ch is None else jnp.asarray(
            np.asarray(ch)[shards["nd_ids"]], dtype)

        def sweep(h_blk, asrc, adst, aw, am, hsrc, hdst, hw, hm):
            h_full = jax.lax.all_gather(h_blk[0], ax, tiled=True)  # (nb_h*S,)
            blk_id = _flat_axis_index(axes)
            hw_g = jnp.take(_mul(h_full[:n_hub], ch_c), asrc[0], axis=0) \
                * (aw[0] * am[0])
            a_blk = _seg_sum(hw_g, adst[0] - blk_id * nb_a, nb_a)
            a_full = jax.lax.all_gather(a_blk, ax, tiled=True)     # (nb_a*S,)
            aw_g = jnp.take(_mul(a_full[:n], ca_j), hsrc[0], axis=0) \
                * (hw[0] * hm[0])
            h_new_blk = _seg_sum(aw_g, hdst[0] - blk_id * nb_h, nb_h)
            tot = jax.lax.psum(jnp.sum(jnp.abs(h_new_blk)), ax)
            h_new_blk = h_new_blk / (tot + 1e-30)
            return h_new_blk[None], a_blk[None]

        smapped = jax.shard_map(
            sweep, mesh=mesh,
            in_specs=(espec,) + (espec,) * 8,
            out_specs=(espec, espec),
        )
        args = tuple(jnp.asarray(a_p[k]) for k in ("src", "dst", "w", "mask")) + \
               tuple(jnp.asarray(h_p[k]) for k in ("src", "dst", "w", "mask"))
        h0 = jnp.full((n_shards, nb_h), 1.0 / n, dtype)
        return smapped, h0, args

    if mode == "dual_blocked":
        nb = int(shards["n_block"])
        a_p, h_p = shards["a"], shards["h"]
        n_pad = nb * n_shards

        def sweep(h_blk, asrc, adst, aw, am, hsrc, hdst, hw, hm):
            # h_blk local view: (1, nb). Rebuild the full (padded) vector.
            h_full = jax.lax.all_gather(h_blk[0], ax, tiled=True)  # (n_pad,)
            blk_id = _flat_axis_index(axes)
            # authority step: scatter into my dst block only
            hw_g = jnp.take(_mul(h_full[:n], ch_j), asrc[0], axis=0) * (aw[0] * am[0])
            a_blk = _seg_sum(hw_g, adst[0] - blk_id * nb, nb)
            a_full = jax.lax.all_gather(a_blk, ax, tiled=True)     # (n_pad,)
            # hub step: h-partition came from g.reverse(): hsrc = orig dst,
            # hdst = orig src (block-local for me).
            aw_g = jnp.take(_mul(a_full[:n], ca_j), hsrc[0], axis=0) * (hw[0] * hm[0])
            h_new_blk = _seg_sum(aw_g, hdst[0] - blk_id * nb, nb)
            tot = jax.lax.psum(jnp.sum(jnp.abs(h_new_blk)), ax)
            h_new_blk = h_new_blk / (tot + 1e-30)
            return h_new_blk[None], a_blk[None]

        smapped = jax.shard_map(
            sweep, mesh=mesh,
            in_specs=(espec,) + (espec,) * 8,
            out_specs=(espec, espec),
        )
        args = tuple(jnp.asarray(a_p[k]) for k in ("src", "dst", "w", "mask")) + \
               tuple(jnp.asarray(h_p[k]) for k in ("src", "dst", "w", "mask"))
        h0 = jnp.full((n_shards, nb), 1.0 / n, dtype)
        del n_pad
        return smapped, h0, args

    raise ValueError(f"unsupported mode {mode}")


# ------------------------------------------------------------- serve path
#
# The serving column sweep (core.hits.hits_sweep_cols) distributes the same
# way as the single-vector ladder above, but with two twists: vectors are
# (N, V) — V independent query columns per traversal — and the per-column
# induced weights/masks change every serving batch, so they must arrive as
# runtime ARGS instead of being baked into the sweep closure.


def build_edge_shards_cols(src, dst, w, n_pad: int, n_shards: int,
                           mode: str = "replicated"):
    """Edge shards for the padded union-subgraph column sweep.

    Unlike ``build_edge_shards`` (whole-crawl preprocessing, exact shapes),
    serving rebuilds shards per batch, so per-shard edge lengths pad to the
    next power of two — the jitted convergence loop compiles once per
    (n_pad, per, V) bucket, not once per query mix. Sentinel edges carry
    w=0 and point at rows whose weights are identically zero, so they
    contribute nothing to either half-step.
    """
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    w = np.asarray(w)
    # strip sentinel (w=0) padding edges up front: under dual_blocked they
    # would all land in the dead pad row's shard and inflate every shard's
    # bucket to ~E_pad (up to S-fold wasted sweep work)
    keep = w != 0
    if not keep.all():
        src, dst, w = src[keep], dst[keep], w[keep]
    e = len(src)

    if mode == "replicated":
        chunk = -(-e // n_shards) if e else 1
        per = next_pow2(chunk)
        s_a = np.full((n_shards, per), n_pad - 1, np.int32)
        d_a = np.full((n_shards, per), n_pad - 1, np.int32)
        w_a = np.zeros((n_shards, per), w.dtype)
        for s in range(n_shards):
            sel = slice(s * chunk, min((s + 1) * chunk, e))
            c = max(sel.stop - sel.start, 0)
            s_a[s, :c] = src[sel]
            d_a[s, :c] = dst[sel]
            w_a[s, :c] = w[sel]
        return {"mode": "replicated", "src": s_a, "dst": d_a, "w": w_a,
                "per": per}

    if mode == "dual_blocked":
        nb = -(-n_pad // n_shards)

        def blocked(key):
            shard_of = key // nb
            order = np.argsort(shard_of, kind="stable")
            counts = np.bincount(shard_of, minlength=n_shards)[:n_shards]
            return order, counts

        a_order, a_counts = blocked(dst)
        h_order, h_counts = blocked(src)
        per = next_pow2(max(int(a_counts.max(initial=1)),
                             int(h_counts.max(initial=1)), 1))

        def pack(order, counts, gather_ids, scatter_ids):
            # scatter ids must stay inside the shard's own block; sentinel
            # scatter = block start, sentinel gather = the dead pad row
            g = np.full((n_shards, per), n_pad - 1, np.int32)
            sc = np.zeros((n_shards, per), np.int32)
            ww = np.zeros((n_shards, per), w.dtype)
            start = 0
            for s in range(n_shards):
                c = int(counts[s])
                sel = order[start:start + c]
                g[s, :c] = gather_ids[sel]
                sc[s, :c] = scatter_ids[sel]
                sc[s, c:] = s * nb
                ww[s, :c] = w[sel]
                start += c
            return {"src": g, "dst": sc, "w": ww}

        return {"mode": "dual_blocked", "nb": nb, "per": per,
                "a": pack(a_order, a_counts, src, dst),   # gather h at src
                "h": pack(h_order, h_counts, dst, src)}   # gather a at dst

    raise ValueError(mode)


def device_put_edge_args_cols(shards, dtype, sharding):
    """Ship ``build_edge_shards_cols`` output to the devices as the sweep's
    edge-argument tuple, in calling-convention order.

    This is the single owner of that ordering — ((src, dst, w) for
    ``replicated``; (asrc, adst, aw, hsrc, hdst, hw) for ``dual_blocked``)
    — and the piece the serve plan cache keeps device-resident, so repeat
    batches over the same union subgraph skip both the host-side
    partition and the host->device transfer. ``sharding`` splits each
    (S, per) plane along its shard axis, so shard s lives on device s.
    """
    def put(x, dt=None):
        return jax.device_put(np.asarray(x, dt), sharding)

    if shards["mode"] == "replicated":
        return (put(shards["src"]), put(shards["dst"]),
                put(shards["w"], dtype))
    if shards["mode"] == "dual_blocked":
        eargs = ()
        for part in (shards["a"], shards["h"]):
            eargs += (put(part["src"]), put(part["dst"]),
                      put(part["w"], dtype))
        return eargs
    raise ValueError(shards["mode"])


def make_dist_hits_sweep_cols(mesh, mode: str, n_pad: int, axes=("data",)):
    """Multi-column (N, V) distributed sweep matching ``hits_sweep_cols``.

    Per-column ca/ch/mask are runtime args (replicated): each half-step's
    scatter output is masked to the column's base set and h is
    L1-normalized per column, so every column computes exactly the induced
    operator of its own focused subgraph — same math, S devices.

    Layouts: ``replicated`` iterates the full (n_pad, V) vector on every
    device (2 psums/sweep, the 4N rung); ``dual_blocked`` iterates a
    (S, nb, V) blocked vector (2 all-gathers/sweep, the 2N rung).
    """
    ax = axes if len(axes) > 1 else axes[0]
    espec = P(ax, None)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))

    if mode == "replicated":

        def sweep(h, ca, ch, m, src, dst, w):
            wm = w[0][:, None]
            a = jax.lax.psum(
                _seg_sum(jnp.take(h * ch, src[0], axis=0) * wm, dst[0], n_pad),
                ax) * m
            h_new = jax.lax.psum(
                _seg_sum(jnp.take(a * ca, dst[0], axis=0) * wm, src[0], n_pad),
                ax) * m
            h_new = h_new / (jnp.sum(jnp.abs(h_new), axis=0, keepdims=True)
                             + 1e-30)
            return h_new, a

        return jax.shard_map(
            sweep, mesh=mesh,
            in_specs=(P(), P(), P(), P(), espec, espec, espec),
            out_specs=(P(), P()))

    if mode == "dual_blocked":
        nb = -(-n_pad // n_shards)
        bspec = P(ax, None, None)

        def sweep(h_blk, ca, ch, m, asrc, adst, aw, hsrc, hdst, hw):
            # h_blk local view: (1, nb, V). Rebuild the full (n_pad, V).
            h_full = jax.lax.all_gather(h_blk[0], ax, tiled=True)
            blk = _flat_axis_index(axes)
            m_blk = jax.lax.dynamic_slice_in_dim(m, blk * nb, nb, axis=0)
            hw_g = jnp.take(h_full * ch, asrc[0], axis=0) * aw[0][:, None]
            a_blk = _seg_sum(hw_g, adst[0] - blk * nb, nb) * m_blk
            a_full = jax.lax.all_gather(a_blk, ax, tiled=True)
            aw_g = jnp.take(a_full * ca, hsrc[0], axis=0) * hw[0][:, None]
            h_new_blk = _seg_sum(aw_g, hdst[0] - blk * nb, nb) * m_blk
            tot = jax.lax.psum(jnp.sum(jnp.abs(h_new_blk), axis=0), ax)
            h_new_blk = h_new_blk / (tot + 1e-30)
            return h_new_blk[None], a_blk[None]

        return jax.shard_map(
            sweep, mesh=mesh,
            in_specs=(bspec, P(), P(), P()) + (espec,) * 6,
            out_specs=(bspec, bspec))

    raise ValueError(f"unsupported mode {mode}")


# ------------------------------------------------- collectives in the HLO
# Per-device collective bytes from a compiled program's optimized HLO text:
# the output bytes of every all-gather / all-reduce / reduce-scatter /
# all-to-all / collective-permute, times the trip count of the while loops
# around it, inferred from each loop's condition.

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """bytes of 'bf16[16,32]' or tuple '(f32[2]{0}, f32[3]{0})'."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> Dict[str, list]:
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo.splitlines():
        m = (re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*"
                      r"\{\s*$", line)
             or re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\{\s*$", line))
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


_WHILE_RE = re.compile(
    r"while\(.*?\),\s*(?:condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)"
    r"|body=%?([\w\.\-]+),\s*condition=%?([\w\.\-]+))")


def _trip_count(cond_lines) -> int:
    best = 1
    for line in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    return best


def collective_bytes(hlo: str) -> dict:
    """Per-device collective bytes (output sizes, trip-count weighted)."""
    comps = _split_computations(hlo)
    # multiplier per computation from (possibly nested) while loops
    mult: Dict[str, float] = {name: 1.0 for name in comps}
    changed = True
    iters = 0
    while changed and iters < 10:
        changed = False
        iters += 1
        for name, lines in comps.items():
            for line in lines:
                for wm in _WHILE_RE.finditer(line):
                    cond = wm.group(1) or wm.group(4)
                    body = wm.group(2) or wm.group(3)
                    trip = _trip_count(comps.get(cond, []))
                    for target in (body, cond):
                        if target in mult:
                            new = mult[name] * trip
                            if new > mult[target]:
                                mult[target] = new
                                changed = True
    per_kind: Dict[str, float] = {}
    count = 0
    for name, lines in comps.items():
        m = mult.get(name, 1.0)
        for line in lines:
            lm = re.match(r"\s*(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(.+?)\s+"
                          r"([a-z\-]+)(?:-start)?\(", line)
            if not lm:
                continue
            op = lm.group(2)
            if op.endswith("-done"):
                continue
            base = None
            for c in _COLLECTIVES:
                if op == c or op == c + "-start":
                    base = c
            if base is None:
                continue
            b = _shape_bytes(lm.group(1)) * m
            per_kind[base] = per_kind.get(base, 0.0) + b
            count += 1
    return {"total_bytes": sum(per_kind.values()), "by_kind": per_kind,
            "n_collective_ops": count}


# ring-algorithm wire bytes per HLO collective OUTPUT byte: an all-reduce
# is reduce-scatter + all-gather (~2(S-1)/S), one-phase collectives (S-1)/S
_RING_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0,
                     "reduce-scatter": 1.0, "all-to-all": 1.0,
                     "collective-permute": 1.0}


def wire_bytes_from_collectives(by_kind: dict, n_shards: int) -> float:
    """Convert ``collective_bytes``'s per-kind output
    sizes into ring wire bytes — the metric the ladder above ranks by."""
    if n_shards <= 1:
        return 0.0
    frac = (n_shards - 1) / n_shards
    return sum(b * frac * _RING_WIRE_FACTOR.get(k, 1.0)
               for k, b in by_kind.items())


def collective_bytes_per_sweep_cols(mode: str, n_pad: int, v: int,
                                    n_shards: int, itemsize: int = 8) -> int:
    """Analytic per-device wire bytes per column sweep — the dist ladder.

    Ring-algorithm model (matching ``wire_bytes_from_collectives``):
    replicated = 2 all-reduces at 2·(S-1)/S bytes per payload byte
    (~4·N·V); dual_blocked = 2 all-gathers at (S-1)/S (~2·N·V).
    """
    if n_shards <= 1:
        return 0
    frac = (n_shards - 1) / n_shards
    payload = n_pad * v * itemsize
    if mode == "replicated":
        return int(2 * 2 * payload * frac)
    if mode == "dual_blocked":
        return int(2 * payload * frac)
    raise ValueError(mode)


def blocked_to_full(h_blk: np.ndarray, n: int) -> np.ndarray:
    """(S, nb) blocked hub vector -> (N,) full vector."""
    return np.asarray(h_blk).reshape(-1)[:n]


def ring_allreduce_chunked(x, axis: str, n_chunks: int = 4):
    """Ring all-reduce via collective_permute, chunked so chunk k's sends
    overlap chunk k+1's adds under XLA's async collective scheduler.
    Semantics == lax.psum(x, axis). Used by the overlap §Perf experiment.
    """
    s = jax.lax.axis_size(axis)
    if s == 1:
        return x
    pad = (-x.shape[0]) % (n_chunks * s)
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    per = xp.shape[0] // n_chunks
    perm = [(i, (i + 1) % s) for i in range(s)]
    me = jax.lax.axis_index(axis)

    def reduce_scatter(buf):  # buf: (s, m) local contributions
        def step(t, b):
            send_idx = (me - t) % s
            recv_idx = (me - t - 1) % s
            chunk = jnp.take(b, send_idx, axis=0)
            received = jax.lax.ppermute(chunk, axis, perm)
            return b.at[recv_idx].add(received)

        buf = jax.lax.fori_loop(0, s - 1, step, buf)
        return jnp.take(buf, (me + 1) % s, axis=0)  # my reduced shard

    outs = []
    for k in range(n_chunks):
        c = jax.lax.dynamic_slice_in_dim(xp, k * per, per, axis=0)
        shard = reduce_scatter(c.reshape(s, -1, *c.shape[1:]))
        gathered = jax.lax.all_gather(shard, axis, tiled=False)  # (s, m…)
        # device d holds shard (d+1)%s: roll so entry j == shard j
        full = jnp.roll(gathered, 1, axis=0).reshape(c.shape)
        outs.append(full)
    return jnp.concatenate(outs, axis=0)[: x.shape[0]]
