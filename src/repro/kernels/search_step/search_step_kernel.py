"""Fused per-hop search kernel + persistent whole-search megakernel.

The paper's headline utilization (§6, contribution 3) comes from a greedy
search kernel that keeps the frontier on-chip and fuses traversal,
distance estimation, and candidate maintenance into one launch. The TPU
translation (docs/megakernel.md):

  * grid = query blocks (the GPU one-block-per-query analogue — here a
    (TQ, ...) tile of queries advances together, vectorized on the VPU);
  * the frontier (ids / dists / visited) lives in VMEM — as pallas values
    inside the per-hop kernel, as VMEM scratch across hops inside the
    megakernel: only the final top-L and per-query hop counts leave chip;
  * adjacency rows, candidate rows (packed RaBitQ codes or f32 vectors),
    per-row metadata, tombstone words and label words stay in HBM as
    lane-dense int32 views (`lane_view`) and are gathered per hop by DMA:
    one 128-lane physical row per candidate and plane, all started
    before any is awaited. Mosaic DMAs only whole lane tiles, so a view
    packs 128/W logical rows of W words into each physical row and the
    kernel selects the wanted slot in VMEM. Row ids reach the scalar
    core through SMEM (VMEM -> SMEM copies of the picked ids and of the
    gathered adjacency rows);
  * scoring reuses the rabitq_dot estimator math on the MXU (one
    (TQ, R, D) x (TQ, D) batch dot per hop); packed code words are
    unpacked by an exact 0/1 byte-spreading matmul, bit-identical to
    `_unpack_tile`;
  * the merge is the kernels/topk min-extraction loop (L argmin+mask
    passes, first-occurrence ties via the iota trick, one-hot column
    writes) — tie semantics identical to `lax.top_k`, so the fused
    frontier matches the unfused merge="topk" path;
  * per-hop beam schedules ride in SMEM: hop t narrows rows that expanded
    work to sched[t] slots after the merge.

One kernel body (`_hop_update`) is traced into both kernels; the per-hop
kernel runs it once per launch, the megakernel loops it under
`fori_loop` + `pl.when(has_work)` so converged blocks retire early while
the lowering stays fixed-trip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_INF = float("inf")  # python float: a jnp scalar here would be a captured
#                      constant inside the kernel closures (pallas rejects)
_LANES = 128
_IDS_PER_WORD = 32   # tombstone bitmap: 32 row bits per int32 word


# ------------------------------------------------------------ lane views
def _row_words(w0: int) -> int:
    """Words per logical row after padding: a power of two below one lane
    tile (so a tile holds a whole number of rows), a multiple of it above."""
    if w0 >= _LANES:
        return -(-w0 // _LANES) * _LANES
    return 1 << (w0 - 1).bit_length()


def lane_view(plane: Array) -> tuple[Array, int]:
    """(rows, k) plane of 4-byte or 1-byte elements -> lane-dense int32
    view (phys_rows, max(W, 128)) and W, the words per logical row.

    Row r lives in physical row r // (128 // W), lanes (r % (128 // W)) * W
    onward. Bytes pack little-endian into words (4 per word), so the view
    holds the plane's bits unchanged plus zero padding: W rounds the row's
    word count up to a power of two (a multiple of 128 past one tile).
    Per-row bytes of the view: 4 * W (adjacency R=64: 256, unchanged;
    4-bit codes D=128: 64, unchanged; D=96: 48 -> 64; f32 rows D=96:
    384 -> 512; RaBitQ meta: 8; exact sqnorm: 4; labels: 4)."""
    rows, k = plane.shape
    size = jnp.dtype(plane.dtype).itemsize
    if size == 4:
        words = jax.lax.bitcast_convert_type(plane, jnp.int32)
    elif size == 1:
        plane = jnp.pad(plane, ((0, 0), (0, (-k) % 4)))
        words = jax.lax.bitcast_convert_type(
            plane.reshape(rows, -1, 4), jnp.int32)
    else:
        raise ValueError(f"lane_view: unsupported dtype {plane.dtype}")
    w0 = words.shape[1]
    w = _row_words(w0)
    per = max(1, _LANES // w)
    words = jnp.pad(words, ((0, (-rows) % per), (0, w - w0)))
    return words.reshape(-1, max(w, _LANES)), w


def bitmap_view(bits: Array) -> Array:
    """Packed row bitmap (nbytes,) u8 -> lane view of its int32 words;
    row r's bit is bit r % 32 of word r // 32."""
    return lane_view(jnp.pad(bits, (0, (-bits.shape[0]) % 4)).reshape(-1, 4))[0]


class Planes(NamedTuple):
    """Static layout of the HBM planes a kernel gathers from."""
    cap: int          # logical rows of the candidate planes
    degree: int       # R, adjacency ids per row
    adj_w: int        # words per logical row, per plane (see lane_view)
    data_w: int
    meta_w: int
    lab_w: int
    dim: int          # exact: D of the f32 rows; quantized: unpacked D


def _per(w: int) -> int:
    return max(1, _LANES // w)


def _lane_pick(g3: Array, lane3: Array) -> Array:
    """(TQ, R, lanes) words, (TQ, R, 1) lane index -> (TQ, R) word.
    A masked integer lane sum: exact, one nonzero term per row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, g3.shape, 2)
    return jnp.sum(jnp.where(lane == lane3, g3, 0), axis=2)


def _slot_rows(g: Array, slot: Array, w: int) -> Array:
    """Physical rows (..., lanes) + slot (..., 1) -> the slot's W words."""
    out = g[..., :w]
    for s in range(1, _per(w)):
        out = jnp.where(slot == s, g[..., s * w:(s + 1) * w], out)
    return out


def _unpack_words(g3: Array, slot3: Array, w: int, bits: int,
                  d_need: int) -> Array:
    """Gathered packed-code rows -> (TQ, R, d_need) f32 codes.

    Code d is bits*(d % cpb) of byte d // cpb of the slot's row; the byte
    sits in word (d // cpb) // 4. Each of the 4 byte planes is spread to
    the code columns by a 0/1 matmul (bf16 in, f32 out: bytes <= 255 and
    one nonzero term per output, so exact — bit-identical to
    `_unpack_tile`); lanes outside the row's slot are zeroed first, so the
    spreading matrix can ignore which slot a row came from."""
    tq, r, lanes = g3.shape
    cpb = 8 // bits
    mask = (1 << bits) - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, g3.shape, 2)
    if _per(w) > 1:
        g3 = jnp.where(lane // w == slot3, g3, 0)
    g2 = g3.reshape(tq * r, lanes)
    li = jax.lax.broadcasted_iota(jnp.int32, (lanes, d_need), 0)
    di = jax.lax.broadcasted_iota(jnp.int32, (lanes, d_need), 1)
    byte = di // cpb
    rep = jnp.zeros((tq * r, d_need), jnp.float32)
    for b in range(4):
        spread = jnp.where((li % w == byte // 4) & (byte % 4 == b),
                           1.0, 0.0).astype(jnp.bfloat16)
        part = ((g2 >> (8 * b)) & 255).astype(jnp.float32)
        rep = rep + jax.lax.dot_general(
            part.astype(jnp.bfloat16), spread, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    shift = bits * (jax.lax.broadcasted_iota(jnp.int32, (1, d_need), 1)
                    % cpb)
    codes = (rep.astype(jnp.int32) >> shift) & mask
    return codes.astype(jnp.float32).reshape(tq, r, d_need)


# ------------------------------------------------------------ hop body
def _merge_topl(all_i: Array, all_d: Array, all_v: Array, l_width: int):
    """Partial top-L via min-extraction (kernels/topk idiom): L sequential
    argmin+mask passes, first-occurrence ties via the column iota — the
    extraction order equals a stable ascending sort by distance, i.e. the
    exact tie semantics of `lax.top_k(-d, L)` in the unfused merge. Pass s
    lands in output column s by a one-hot select (Mosaic stores only at
    lane offsets it can prove)."""
    tq, c = all_d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, c), 1)
    ocol = jax.lax.broadcasted_iota(jnp.int32, (tq, l_width), 1)

    def step(s, carry):
        work, oi, od, ov = carry
        m = jnp.min(work, axis=1, keepdims=True)               # (TQ, 1)
        first = jnp.min(jnp.where(work == m, col, c), axis=1,
                        keepdims=True)
        sel = col == first
        pick_i = jnp.sum(jnp.where(sel, all_i, 0), axis=1, keepdims=True)
        pick_v = jnp.sum(jnp.where(sel, all_v, 0), axis=1, keepdims=True)
        at = ocol == s
        oi = jnp.where(at, pick_i, oi)
        od = jnp.where(at, m, od)
        ov = jnp.where(at, pick_v, ov)
        return jnp.where(sel, _INF, work), oi, od, ov

    init = (all_d,
            jnp.full((tq, l_width), -1, jnp.int32),
            jnp.full((tq, l_width), _INF, jnp.float32),
            jnp.zeros((tq, l_width), jnp.int32))
    _, oi, od, ov = jax.lax.fori_loop(0, l_width, step, init)
    return oi, od, ov


def _gather(cur, refs, scratch, pl_: Planes, *, use_tomb: bool,
            use_filt: bool):
    """DMA gathers of one hop: the picked rows' adjacency, then every
    candidate's data / meta / tombstone / label rows. Returns the raw
    candidate ids (TQ, R) and the gathered physical rows."""
    adj_ref, data_ref, meta_ref, tomb_ref, lab_ref = refs
    (cur_v, cur_s, adj_buf, adj_s, data_buf, meta_buf, tomb_buf, lab_buf,
     sems) = scratch
    tq = cur.shape[0]
    degree, cap = pl_.degree, pl_.cap
    per_adj = _per(pl_.adj_w)

    # picked ids -> SMEM, then one adjacency row DMA per query
    cur_v[...] = jnp.broadcast_to(cur, cur_v.shape)
    pltpu.sync_copy(cur_v, cur_s)
    copies = []
    for i in range(tq):
        c = jnp.clip(cur_s[i, 0], 0, cap - 1)
        cp = pltpu.make_async_copy(adj_ref.at[pl.ds(c // per_adj, 1), :],
                                   adj_buf.at[pl.ds(i, 1), :], sems.at[0])
        cp.start()
        copies.append(cp)
    for cp in copies:
        cp.wait()
    pltpu.sync_copy(adj_buf, adj_s)
    slot = jnp.clip(cur, 0, cap - 1) % per_adj
    raw = _slot_rows(adj_buf[...], slot, pl_.adj_w)[:, :degree]

    # one DMA per candidate and plane, all in flight before any wait
    planes = [(data_ref, data_buf, pl_.data_w, 0),
              (meta_ref, meta_buf, pl_.meta_w, 0)]
    if use_tomb:
        planes.append((tomb_ref, tomb_buf, 1, 5))
    if use_filt:
        planes.append((lab_ref, lab_buf, pl_.lab_w, 0))

    def cand_copies(n, rid):
        out = []
        for k, (src, dst, w, shift) in enumerate(planes):
            out.append(pltpu.make_async_copy(
                src.at[pl.ds((rid >> shift) // _per(w), 1), :],
                dst.at[pl.ds(n, 1), :], sems.at[k + 1]))
        return out

    def start(n, carry):
        i = n // degree
        c = jnp.clip(cur_s[i, 0], 0, cap - 1)
        rid = adj_s[i, (c % per_adj) * pl_.adj_w + n % degree]
        for cp in cand_copies(n, jnp.clip(rid, 0, cap - 1)):
            cp.start()
        return carry

    def wait(n, carry):
        for cp in cand_copies(0, 0):
            cp.wait()
        return carry

    n_cand = tq * degree
    jax.lax.fori_loop(0, n_cand, start, 0)
    jax.lax.fori_loop(0, n_cand, wait, 0)

    def rows3(buf):
        return buf[...].reshape(tq, degree, buf.shape[1])

    return (raw, rows3(data_buf), rows3(meta_buf),
            rows3(tomb_buf) if use_tomb else None,
            rows3(lab_buf) if use_filt else None)


def _hop_update(f_ids, f_dists, f_vis, width, q, qa, qb, nvalid, fbw,
                refs, scratch, pl_: Planes, *, quantized: bool, bits: int,
                use_tomb: bool, use_filt: bool, telemetry: bool = False):
    """One fused hop over a (TQ, L) frontier block — pure values in/out,
    HBM views + VMEM/SMEM scratch for the gathers. Shared by both kernels.

    q/qa/qb: quantized -> (q_rot, query_add, query_sumq);
             exact     -> (queries, |q|^2, unused).
    fbw: label word masks (exclude-mode filter; dead unless `use_filt`).
    Returns (f_ids, f_dists, f_vis, pick_valid (TQ, 1)) — plus, with
    `telemetry`, a fifth element (scored, masked, dups, occ) of (TQ, 1)
    i32 hop counters (semantics: core.beam_search.SearchTelemetry;
    contract: the ref oracle's values, exactly)."""
    tq, l_width = f_ids.shape
    degree, cap = pl_.degree, pl_.cap
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, l_width), 1)

    # ---- pick: first unvisited slot (frontier is distance-sorted)
    unvis = (f_ids >= 0) & (f_vis == 0)
    order = jnp.where(unvis, col, l_width)
    pick = jnp.min(order, axis=1, keepdims=True)           # (TQ, 1)
    pick_valid = pick < l_width
    safe_pos = jnp.minimum(pick, l_width - 1)
    sel = col == safe_pos
    cur = jnp.sum(jnp.where(sel, f_ids, 0), axis=1, keepdims=True)
    cur = jnp.where(pick_valid, cur, -1)
    f_vis = jnp.where(sel & unvis & pick_valid, 1, f_vis)

    # ---- expand + gather: adjacency rows, then every candidate's planes
    raw, g_data, g_meta, g_tomb, g_lab = _gather(
        cur, refs, scratch, pl_, use_tomb=use_tomb, use_filt=use_filt)
    nbrs = jnp.where(cur >= 0, raw, -1)                    # (TQ, R)
    in_range = (nbrs >= 0) & (nbrs < nvalid)
    dup = jnp.any(nbrs[:, :, None] == f_ids[:, None, :], axis=2)
    valid = in_range & ~dup
    rid3 = jnp.clip(raw, 0, cap - 1)[:, :, None]           # gathered rows
    if use_tomb:
        # exclude-mode liveness: one word per candidate, bit test fused
        # right here (never a dense bitmap unpack)
        word = _lane_pick(g_tomb, (rid3 >> 5) % _LANES)
        bit = (word >> (jnp.maximum(nbrs, 0) & (_IDS_PER_WORD - 1))) & 1
        dead = valid & (bit == 1)
        valid &= bit == 0
    if use_filt:
        # exclude-mode label filter: the candidate's label words AND the
        # query mask. Runs AFTER the tombstone test so a dead candidate
        # counts once in the masked telemetry, whatever its labels say.
        slot = rid3 % _per(pl_.lab_w)
        hit = jnp.zeros(nbrs.shape, jnp.bool_)
        for c in range(pl_.lab_w):
            w = _lane_pick(g_lab, slot * pl_.lab_w + c)
            hit |= (w & fbw[c]) != 0
        fmiss = valid & ~hit
        valid &= ~fmiss

    # ---- score: MXU batch dot over the gathered candidate rows
    slot3 = rid3 % _per(pl_.data_w)
    meta_slot = (rid3 % _per(pl_.meta_w)) * pl_.meta_w

    def meta(c):
        return jax.lax.bitcast_convert_type(
            _lane_pick(g_meta, meta_slot + c), jnp.float32)

    if quantized:
        codes = _unpack_words(g_data, slot3, pl_.data_w, bits, pl_.dim)
        dot = jax.lax.dot_general(
            codes, q, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # (TQ, R)
        d = meta(0) + qa + meta(1) * (dot - qb)
    else:
        rows = _slot_rows(g_data, slot3, pl_.data_w)[..., :pl_.dim]
        cand = jax.lax.bitcast_convert_type(rows, jnp.float32)
        dot = jax.lax.dot_general(
            cand, q, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        d = qa - 2.0 * dot + meta(0)
    d = jnp.maximum(d, 0.0)
    c_ids = jnp.where(valid, nbrs, -1)
    c_d = jnp.where(valid, d, _INF)

    # ---- merge: partial top-L over frontier ++ candidates
    all_i = jnp.concatenate([f_ids, c_ids], axis=1)
    all_d = jnp.concatenate([f_dists, c_d], axis=1)
    all_v = jnp.concatenate([f_vis, jnp.zeros((tq, degree), jnp.int32)],
                            axis=1)
    nfi, nfd, nfv = _merge_topl(all_i, all_d, all_v, l_width)

    # ---- per-hop beam narrowing (rows that expanded work only)
    keep = (col < width) | ~pick_valid
    nfi = jnp.where(keep, nfi, -1)
    nfd = jnp.where(keep, nfd, _INF)
    nfv = jnp.where(keep, nfv, 0)
    if telemetry:
        def count(x):
            return jnp.sum(x.astype(jnp.int32), axis=1, keepdims=True)
        scored = count(valid)
        masked = count(dead) if use_tomb else jnp.zeros_like(scored)
        if use_filt:
            masked = masked + count(fmiss)
        dups = count(in_range & dup)
        occ = jnp.where(pick_valid, count(nfi >= 0), 0)
        return nfi, nfd, nfv, pick_valid, (scored, masked, dups, occ)
    return nfi, nfd, nfv, pick_valid


# ------------------------------------------------------------ kernels
_N_PLANE_REFS = 5   # adjacency, data, meta, tombstones, labels


def _fb_words(fb_ref, pl_: Planes):
    return [fb_ref[c] for c in range(pl_.lab_w)]


def _hop_kernel(w_ref, nvalid_ref, fb_ref, q_ref, qa_ref, qb_ref, fi_ref,
                fd_ref, fv_ref, *rest, planes: Planes, quantized: bool,
                bits: int, use_tomb: bool, use_filt: bool,
                telemetry: bool = False):
    """Stage 1: ONE launch per hop — frontier in/out through VMEM blocks,
    all gathers + scoring + merge fused inside. With telemetry, one extra
    (TQ, 4) i32 output of [scored, masked, dups, occupancy] hop counters;
    without, the signature (and the compiled plan) is unchanged."""
    refs, rest = rest[:_N_PLANE_REFS], rest[_N_PLANE_REFS:]
    n_out = 5 if telemetry else 4
    outs, scratch = rest[:n_out], rest[n_out:]
    ofi_ref, ofd_ref, ofv_ref, oh_ref = outs[:4]
    up = _hop_update(
        fi_ref[...], fd_ref[...], fv_ref[...], w_ref[0],
        q_ref[...], qa_ref[...], qb_ref[...], nvalid_ref[0],
        _fb_words(fb_ref, planes), refs, scratch, planes,
        quantized=quantized, bits=bits, use_tomb=use_tomb,
        use_filt=use_filt, telemetry=telemetry)
    nfi, nfd, nfv, pv = up[:4]
    ofi_ref[...] = nfi
    ofd_ref[...] = nfd
    ofv_ref[...] = nfv
    oh_ref[...] = pv.astype(jnp.int32)
    if telemetry:
        outs[4][...] = jnp.concatenate(up[4], axis=1)


def _mega_kernel(sched_ref, nvalid_ref, fb_ref, q_ref, qa_ref, qb_ref,
                 fi_ref, fd_ref, fv_ref, *rest, planes: Planes,
                 quantized: bool, bits: int, use_tomb: bool, use_filt: bool,
                 max_iters: int, telemetry: bool = False):
    """Stage 2: the whole beam loop in ONE persistent launch.

    Frontier ids/dists/visited and hop counters live in VMEM scratch
    across hops; the fori_loop body is guarded by `pl.when(has_work)` so a
    converged block retires into no-op trips (fixed-trip lowering, early
    convergence — the same accounting contract as the unfused loop: hops
    count expansions performed, never loop trips).

    With telemetry, two extra outputs — (TQ, 3) summed counters and a
    (TQ, max_iters) per-hop occupancy log — accumulate in extra VMEM
    scratch; a retired block stops writing, leaving the log's tail at its
    zero init (exactly the unfused loop's untouched entries). `rest` is
    plane refs, then outputs, then scratch — the last two lists
    telemetry-dependent."""
    refs, rest = rest[:_N_PLANE_REFS], rest[_N_PLANE_REFS:]
    if telemetry:
        (ofi_ref, ofd_ref, oh_ref, oc_ref, oocc_ref,
         fi_s, fd_s, fv_s, h_s, c_s, occ_s), gather_s = rest[:11], rest[11:]
    else:
        (ofi_ref, ofd_ref, oh_ref, fi_s, fd_s, fv_s, h_s), gather_s = (
            rest[:7], rest[7:])
    fi_s[...] = fi_ref[...]
    fd_s[...] = fd_ref[...]
    fv_s[...] = fv_ref[...]
    h_s[...] = jnp.zeros_like(h_s)
    if telemetry:
        c_s[...] = jnp.zeros_like(c_s)
        occ_s[...] = jnp.zeros_like(occ_s)
        occ_col = jax.lax.broadcasted_iota(jnp.int32, occ_s.shape, 1)

    def step(t, carry):
        f_ids = fi_s[...]
        f_vis = fv_s[...]
        has = jnp.any((f_ids >= 0) & (f_vis == 0))

        @pl.when(has)
        def _():
            up = _hop_update(
                f_ids, fd_s[...], f_vis, sched_ref[t],
                q_ref[...], qa_ref[...], qb_ref[...], nvalid_ref[0],
                _fb_words(fb_ref, planes), refs, gather_s, planes,
                quantized=quantized, bits=bits, use_tomb=use_tomb,
                use_filt=use_filt, telemetry=telemetry)
            nfi, nfd, nfv, pv = up[:4]
            fi_s[...] = nfi
            fd_s[...] = nfd
            fv_s[...] = nfv
            h_s[...] = h_s[...] + pv.astype(jnp.int32)
            if telemetry:
                scored, masked, dups, occ = up[4]
                c_s[...] = c_s[...] + jnp.concatenate(
                    [scored, masked, dups], axis=1)
                occ_s[...] = jnp.where(occ_col == t, occ, occ_s[...])

        return carry

    jax.lax.fori_loop(0, max_iters, step, 0)
    ofi_ref[...] = fi_s[...]
    ofd_ref[...] = fd_s[...]
    oh_ref[...] = h_s[...]
    if telemetry:
        oc_ref[...] = c_s[...]
        oocc_ref[...] = occ_s[...]


# ------------------------------------------------------------ launchers
def fused_planes(adjacency: Array, data: Array, meta: Array,
                 tomb: Array | None, labels: Array | None,
                 fb: Array | None, *, dim: int):
    """HBM planes -> (lane views, static `Planes`, label word masks).

    adjacency (cap, R) i32; data: packed codes (cap, P) u8 or f32 rows
    (cap, D); meta: (cap, 2) RaBitQ (add, rescale) or (cap, 1) sqnorms;
    tomb: packed bitmap (exclude mode) or None; labels/fb: (cap, NB) u8
    label plane + (NB,) byte mask (exclude-mode filter) or None. Unused
    planes become one-tile dummies, so the launch signature is fixed.
    dim: exact D, or the unpacked code width P * (8 // bits)."""
    dummy = jnp.zeros((1, _LANES), jnp.int32)
    adj_v, adj_w = lane_view(adjacency)
    data_v, data_w = lane_view(data)
    meta_v, meta_w = lane_view(meta)
    tomb_v = dummy if tomb is None else bitmap_view(tomb)
    lab_v, lab_w = (dummy, 1) if labels is None else lane_view(labels)
    fbw = jnp.zeros((lab_w,), jnp.int32)
    if fb is not None:
        nb = fb.shape[0]
        fbb = jnp.pad(fb.astype(jnp.uint8), (0, (-nb) % 4)).reshape(-1, 4)
        words = jax.lax.bitcast_convert_type(fbb, jnp.int32)
        fbw = fbw.at[:words.shape[0]].set(words)
    planes = Planes(cap=data.shape[0], degree=adjacency.shape[1],
                    adj_w=adj_w, data_w=data_w, meta_w=meta_w,
                    lab_w=lab_w, dim=dim)
    return (adj_v, data_v, meta_v, tomb_v, lab_v), planes, fbw


def _launch(kernel, *, block_q: int, smem_args, q_args, frontier, views,
            planes: Planes, outs, scratch, use_tomb: bool, use_filt: bool,
            interpret):
    """Shared pallas_call plumbing of both kernels: SMEM scalars, the
    (block_q, ·) query and frontier blocks, the HBM plane views, and the
    gather landing buffers appended after the kernel's own scratch."""
    qn, l_width = frontier[0].shape
    d = q_args[0].shape[1]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    anys = pl.BlockSpec(memory_space=pl.ANY)

    def blk(w):
        return pl.BlockSpec((block_q, w), lambda i: (i, 0))

    in_specs = ([smem] * len(smem_args) + [blk(d), blk(1), blk(1)]
                + [blk(l_width)] * 3 + [anys] * _N_PLANE_REFS)
    n_cand = block_q * planes.degree
    adj, data, meta, tomb, lab = views
    scratch = list(scratch) + [
        pltpu.VMEM((block_q, _LANES), jnp.int32),        # picked ids
        pltpu.SMEM((block_q, _LANES), jnp.int32),
        pltpu.VMEM((block_q, adj.shape[1]), jnp.int32),  # adjacency rows
        pltpu.SMEM((block_q, adj.shape[1]), jnp.int32),
        pltpu.VMEM((n_cand, data.shape[1]), jnp.int32),  # candidate rows
        pltpu.VMEM((n_cand, meta.shape[1]), jnp.int32),  # candidate meta
        pltpu.VMEM((n_cand if use_tomb else 8, tomb.shape[1]), jnp.int32),
        pltpu.VMEM((n_cand if use_filt else 8, lab.shape[1]), jnp.int32),
        pltpu.SemaphoreType.DMA((_N_PLANE_REFS,)),
    ]
    return pl.pallas_call(
        kernel,
        grid=(qn // block_q,),
        in_specs=in_specs,
        out_specs=[blk(w) for w, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((qn, w), dt) for w, dt in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*smem_args, *q_args, *frontier, *views)


def fused_hop_pallas(f_ids, f_dists, f_vis, width, q, qa, qb, views,
                     planes: Planes, fbw, n_valid, *, quantized: bool,
                     bits: int, use_tomb: bool, use_filt: bool,
                     block_q: int = 8, telemetry: bool = False,
                     interpret=False):
    """One fused hop. All (Q, ·) arrays pre-padded to block_q rows;
    views/planes/fbw from `fused_planes`.
    Returns (f_ids, f_dists, f_vis, hop_inc (Q, 1)) — plus a (Q, 4) i32
    [scored, masked, dups, occupancy] counter block with telemetry on
    (off: zero extra outputs, the pallas_call is identical)."""
    l_width = f_ids.shape[1]
    outs = [(l_width, jnp.int32), (l_width, jnp.float32),
            (l_width, jnp.int32), (1, jnp.int32)]
    if telemetry:
        outs.append((4, jnp.int32))
    kern = functools.partial(
        _hop_kernel, planes=planes, quantized=quantized, bits=bits,
        use_tomb=use_tomb, use_filt=use_filt, telemetry=telemetry)
    return _launch(
        kern, block_q=block_q,
        smem_args=(jnp.asarray(width, jnp.int32).reshape(1),
                   jnp.asarray(n_valid, jnp.int32).reshape(1), fbw),
        q_args=(q, qa, qb), frontier=(f_ids, f_dists, f_vis), views=views,
        planes=planes, outs=outs, scratch=(), use_tomb=use_tomb,
        use_filt=use_filt, interpret=interpret)


def fused_search_pallas(f_ids, f_dists, f_vis, schedule, q, qa, qb, views,
                        planes: Planes, fbw, n_valid, *, quantized: bool,
                        bits: int, use_tomb: bool, use_filt: bool,
                        max_iters: int, block_q: int = 8,
                        telemetry: bool = False, interpret=False):
    """The megakernel: whole search, one launch. schedule: (max_iters,)
    i32 per-hop widths; views/planes/fbw as in `fused_hop_pallas`.
    Returns (f_ids, f_dists, n_hops (Q, 1)) — plus
    (counters (Q, 3) i32 [scored, masked, dups], occupancy
    (Q, max_iters) i32) with telemetry on, accumulated in VMEM scratch
    across hops (off: zero extra outputs/scratch, identical launch)."""
    l_width = f_ids.shape[1]
    outs = [(l_width, jnp.int32), (l_width, jnp.float32), (1, jnp.int32)]
    scratch = [
        pltpu.VMEM((block_q, l_width), jnp.int32),    # frontier ids
        pltpu.VMEM((block_q, l_width), jnp.float32),  # frontier dists
        pltpu.VMEM((block_q, l_width), jnp.int32),    # visited flags
        pltpu.VMEM((block_q, 1), jnp.int32),          # hop counters
    ]
    if telemetry:
        outs += [(3, jnp.int32), (max_iters, jnp.int32)]
        scratch += [
            pltpu.VMEM((block_q, 3), jnp.int32),          # counter sums
            pltpu.VMEM((block_q, max_iters), jnp.int32),  # occupancy log
        ]
    kern = functools.partial(
        _mega_kernel, planes=planes, quantized=quantized, bits=bits,
        use_tomb=use_tomb, use_filt=use_filt, max_iters=max_iters,
        telemetry=telemetry)
    return _launch(
        kern, block_q=block_q,
        smem_args=(jnp.asarray(schedule, jnp.int32).reshape(-1),
                   jnp.asarray(n_valid, jnp.int32).reshape(1), fbw),
        q_args=(q, qa, qb), frontier=(f_ids, f_dists, f_vis), views=views,
        planes=planes, outs=outs, scratch=scratch, use_tomb=use_tomb,
        use_filt=use_filt, interpret=interpret)
