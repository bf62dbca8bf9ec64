"""RaBitQ quantization (paper §5.1, Gao & Long 2024), TPU-adapted.

RaBitQ compresses a vector v by (1) centering (v - c), (2) applying a random
orthonormal rotation P, (3) normalizing to a unit vector o, and (4) scalar-
quantizing each coordinate to m bits. Johnson–Lindenstrauss concentration
makes rotated unit-vector coordinates ~N(0, 1/D), so a shared per-vector
uniform quantizer is unbiased and tight.

Distance estimation (Table 2 of the paper): squared L2 between v and q
collapses to ONE inner product between the integer codes and the rotated
query, plus per-vector / per-query scalar metadata:

    d^2(v, q) ~= data_add + query_add
                 + data_rescale * (<codes, q_rot> - query_sumq)

with
    o        = P(v - c) / |v - c|
    delta    = 2 * max_i |o_i| / (2^m - 1)          (per-vector step)
    codes    = clip(round(o / delta + (2^m-1)/2), 0, 2^m-1)
    o_bar    = delta * (codes - (2^m-1)/2)           (dequantized o)
    data_add     = |v - c|^2
    data_rescale = -2 * |v - c| * delta / <o_bar, o>
    q_rot        = P(q - c)
    query_add    = |q - c|^2
    query_sumq   = (2^m - 1)/2 * sum(q_rot)

This is algebraically the estimator the paper tabulates (the paper's
data_add/data_rescale fold the same factors differently; we re-derive from
first principles and validate the O(1/sqrt(D)) error bound in tests).

TPU adaptation (DESIGN.md §2): the GPU implementation exploits sequential
16-byte loads; on TPU the estimator inner product <codes, q_rot> over a tile
of candidates IS a matmul (C_tile x D) @ (D x Q_tile) and runs on the MXU —
see kernels/rabitq_dot. The PACKED form (pack_codes) is the canonical
device-resident representation: RaBitQCodes stores one uint8 row of
ceil(D*m/8) code bytes plus the 8 bytes of the two f32 factors per vector
and nothing wider, so the 8x/4x/2x memory-footprint reduction the paper
reports is what actually sits in HBM, and a candidate's code and factors
arrive in one row gather. Consumers either unpack on the fly
(jnp reference paths) or unpack in-kernel with shift/mask VPU ops (the TPU
analogue of the paper's in-warp bit arithmetic).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 1e-12

SUPPORTED_BITS = (1, 2, 4, 8)


@partial(jax.tree_util.register_dataclass,
         data_fields=("rotation", "centroid"), meta_fields=("bits",))
@dataclass(frozen=True)
class RaBitQParams:
    """Dataset-level quantizer state (trained once, tiny).

    ``bits`` is pytree *metadata* so it stays a static python int under jit.
    """

    rotation: Array   # (D, D) orthonormal
    centroid: Array   # (D,)
    bits: int         # m — static python int

    @property
    def dims(self) -> int:
        return self.rotation.shape[0]


@partial(jax.tree_util.register_dataclass,
         data_fields=("rows",), meta_fields=("bits", "dims"))
@dataclass(frozen=True)
class RaBitQCodes:
    """Per-vector quantized storage: one code row per vector.

    rows:        uint8[N, P + 8] with P = ceil(D*bits/8): the bit-packed
                 integer codes in bytes [0, P) (layout: pack_codes), then
                 data_add and data_rescale as little-endian f32 bit
                 patterns in bytes [P, P + 4) and [P + 4, P + 8). Scoring
                 a candidate is one row gather; its factors come with it.
    bits / dims: pytree metadata (static python ints under jit)

    `packed`, `data_add` and `data_rescale` are read-only views of the rows.
    """

    rows: Array
    bits: int
    dims: int

    @classmethod
    def from_parts(cls, packed: Array, data_add: Array, data_rescale: Array,
                   bits: int, dims: int) -> "RaBitQCodes":
        """Rows from packed uint8[N, P] codes and the two f32[N] factors."""
        row = [packed, _f32_bytes(data_add), _f32_bytes(data_rescale)]
        return cls(rows=jnp.concatenate(row, axis=-1), bits=bits, dims=dims)

    @property
    def code_width(self) -> int:
        """P: the packed code bytes at the head of each row."""
        return packed_dim(self.dims, self.bits)

    @property
    def packed(self) -> Array:
        return self.rows[..., :self.code_width]

    @property
    def data_add(self) -> Array:
        p = self.code_width
        return _bytes_f32(self.rows[..., p:p + 4])

    @property
    def data_rescale(self) -> Array:
        p = self.code_width
        return _bytes_f32(self.rows[..., p + 4:p + 8])

    def unpacked(self) -> Array:
        """Transient uint8[N, D] view (materialized on demand, never stored)."""
        return unpack_codes(self.packed, self.bits, self.dims)

    def gather_packed(self, ids: Array) -> tuple[Array, Array, Array]:
        """One row gather: ids[...] -> (packed uint8[..., P], data_add
        f32[...], data_rescale f32[...]). It moves P + 8 bytes per row."""
        g = self.rows[ids]
        p = self.code_width
        return (g[..., :p], _bytes_f32(g[..., p:p + 4]),
                _bytes_f32(g[..., p + 4:p + 8]))

    def gather(self, ids: Array) -> tuple[Array, Array, Array]:
        """gather_packed, then unpack the code bytes alone: ids[...] ->
        (uint8[..., D] codes, data_add, data_rescale). The unpack is cheap
        VPU shift/mask work and never sees the factor bytes."""
        packed, add, rescale = self.gather_packed(ids)
        return unpack_codes(packed, self.bits, self.dims), add, rescale


def _f32_bytes(x: Array) -> Array:
    """f32[...] -> uint8[..., 4]: the little-endian bytes of its bits."""
    word = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8
    return ((word[..., None] >> shifts) & 0xFF).astype(jnp.uint8)


def _bytes_f32(b: Array) -> Array:
    """Inverse of _f32_bytes: uint8[..., 4] -> f32[...], bit for bit."""
    u = b.astype(jnp.uint32)
    word = u[..., 0] | (u[..., 1] << 8) | (u[..., 2] << 16) | (u[..., 3] << 24)
    return jax.lax.bitcast_convert_type(word, jnp.float32)


class RaBitQQuery(NamedTuple):
    """Per-query preprocessed state (computed once per query batch)."""

    q_rot: Array       # (Q, D) rotated, centered query
    query_add: Array   # (Q,)
    query_sumq: Array  # (Q,)


def random_rotation(key: Array, dims: int) -> Array:
    """Random orthonormal matrix via QR of a Gaussian (Haar measure)."""
    g = jax.random.normal(key, (dims, dims), dtype=jnp.float32)
    q, r = jnp.linalg.qr(g)
    # Fix signs so the distribution is exactly Haar (and deterministic).
    d = jnp.sign(jnp.diagonal(r))
    return q * d[None, :]


def rabitq_train(key: Array, vectors: Array, bits: int = 4,
                 valid_mask: Array | None = None) -> RaBitQParams:
    """Fit the (trivial) trainable state: centroid + rotation."""
    if bits not in SUPPORTED_BITS:
        raise ValueError(f"bits must be one of {SUPPORTED_BITS}, got {bits}")
    v = vectors.astype(jnp.float32)
    if valid_mask is None:
        centroid = jnp.mean(v, axis=0)
    else:
        w = valid_mask.astype(jnp.float32)
        centroid = jnp.sum(v * w[:, None], axis=0) / jnp.maximum(jnp.sum(w), 1.0)
    rot = random_rotation(key, v.shape[1])
    return RaBitQParams(rotation=rot, centroid=centroid, bits=bits)


def _rotate(r: Array, rotation: Array) -> Array:
    """P r for each row of r, in float32: at default precision a TPU runs
    the product as one bfloat16 pass, which would round float rows and
    queries below the float32 the codes are computed for."""
    return jnp.matmul(r, rotation.T, precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("bits",))
def _encode(vectors: Array, rotation: Array, centroid: Array, bits: int) -> RaBitQCodes:
    levels = float(2**bits - 1)
    half = levels / 2.0
    r = vectors.astype(jnp.float32) - centroid[None, :]
    norm2 = jnp.sum(r * r, axis=-1)                      # |v-c|^2
    norm = jnp.sqrt(norm2)
    o_un = _rotate(r, rotation)                          # P(v-c)
    o = o_un / jnp.maximum(norm, _EPS)[:, None]          # unit
    delta = 2.0 * jnp.max(jnp.abs(o), axis=-1) / levels  # per-vector step
    delta = jnp.maximum(delta, _EPS)
    u = jnp.clip(jnp.round(o / delta[:, None] + half), 0.0, levels)
    o_bar = delta[:, None] * (u - half)
    ip = jnp.sum(o_bar * o, axis=-1)                     # <o_bar, o>
    rescale = -2.0 * norm * delta / jnp.where(jnp.abs(ip) > _EPS, ip, 1.0)
    rescale = jnp.where(norm > _EPS, rescale, 0.0)
    # encode -> pack fused under one jit: the unpacked uint8[N, D] form is a
    # transient value inside this trace, never a resident buffer
    return RaBitQCodes.from_parts(pack_codes(u.astype(jnp.uint8), bits),
                                  norm2, rescale, bits, vectors.shape[1])


def rabitq_encode(params: RaBitQParams, vectors: Array) -> RaBitQCodes:
    """Quantize (N, D) vectors -> packed codes + metadata."""
    return _encode(vectors, params.rotation, params.centroid, params.bits)


@partial(jax.jit, static_argnames=("bits",))
def _preprocess_query(queries: Array, rotation: Array, centroid: Array,
                      bits: int) -> RaBitQQuery:
    half = (2**bits - 1) / 2.0
    r = queries.astype(jnp.float32) - centroid[None, :]
    q_rot = _rotate(r, rotation)
    return RaBitQQuery(
        q_rot=q_rot,
        query_add=jnp.sum(r * r, axis=-1),
        query_sumq=half * jnp.sum(q_rot, axis=-1),
    )


def rabitq_preprocess_query(params: RaBitQParams, queries: Array) -> RaBitQQuery:
    """Rotate/center queries and compute the two query-side scalars."""
    return _preprocess_query(queries, params.rotation, params.centroid, params.bits)


def rabitq_estimate(codes: RaBitQCodes, query: RaBitQQuery,
                    candidate_ids: Array | None = None) -> Array:
    """Estimated squared L2 distances.

    With candidate_ids (Q, K): per-query candidate sets (beam search form),
    returns (Q, K). Without: all-pairs (Q, N) — one big MXU matmul (used by
    brute-force rerank and tests).
    """
    if candidate_ids is None:
        dot = query.q_rot @ codes.unpacked().astype(jnp.float32).T  # (Q, N)
        add = codes.data_add[None, :]
        rsc = codes.data_rescale[None, :]
    else:
        safe = jnp.maximum(candidate_ids, 0)
        # one gather of PACKED rows (the bytes that actually move) brings
        # the codes and both factors; the codes are unpacked after
        c, add, rsc = codes.gather(safe)
        dot = jnp.einsum("qkd,qd->qk", c.astype(jnp.float32), query.q_rot)
    est = add + query.query_add[..., None] + rsc * (dot - query.query_sumq[..., None])
    return jnp.maximum(est, 0.0)


# ---------------------------------------------------------------------------
# Bit packing — the HBM/wire representation ("built for speed": the memory
# footprint reduction the paper reports is on this packed form).
# ---------------------------------------------------------------------------

def packed_dim(dims: int, bits: int) -> int:
    cpb = 8 // bits
    return (dims + cpb - 1) // cpb


def pack_codes(codes: Array, bits: int) -> Array:
    """uint8[..., D] (values < 2^m) -> uint8[..., ceil(D*m/8)].

    Little-endian within each byte: code j of a byte occupies bits
    [j*m, (j+1)*m). D is zero-padded to a multiple of (8//m). Leading
    dimensions are preserved (rows pack independently).
    """
    if bits not in SUPPORTED_BITS:
        raise ValueError(f"bits must be one of {SUPPORTED_BITS}")
    cpb = 8 // bits
    d = codes.shape[-1]
    d_pad = packed_dim(d, bits) * cpb
    widths = [(0, 0)] * (codes.ndim - 1) + [(0, d_pad - d)]
    c = jnp.pad(codes, widths).astype(jnp.uint32)
    c = c.reshape(*codes.shape[:-1], d_pad // cpb, cpb)
    shifts = jnp.arange(cpb, dtype=jnp.uint32) * bits
    packed = jnp.sum(c << shifts, axis=-1)
    return packed.astype(jnp.uint8)


def unpack_codes(packed: Array, bits: int, dims: int) -> Array:
    """Inverse of pack_codes -> uint8[..., dims] (leading dims preserved)."""
    cpb = 8 // bits
    mask = jnp.uint32(2**bits - 1)
    p = packed.astype(jnp.uint32)[..., None]
    shifts = jnp.arange(cpb, dtype=jnp.uint32) * bits
    u = (p >> shifts) & mask
    u = u.reshape(*packed.shape[:-1], -1)[..., :dims]
    return u.astype(jnp.uint8)


def packed_bytes_per_vector(dims: int, bits: int) -> int:
    """Storage per vector incl. the two f32 metadata (paper's size formula)."""
    return packed_dim(dims, bits) + 2 * 4
