"""Pluggable plane codecs: how packed bit planes are *stored* on crossbars.

Everything upstream of this module treats the canonical packed planes
(``uint8[S, W, cols]``, ``bitslice.section_planes_packed``) as the literal
crossbar content.  That is one point in a design space: the column-similarity
reordering line of work (PAPERS.md, arXiv:2511.14202) stores each section's
bit columns in a *permuted* physical order so consecutive reprograms realign
similar columns onto the same bit line, and the near-constant high-order
planes that Sorted Weight Sectioning concentrates can be stored as one byte
plus a flag instead of ``W`` words.  This module makes the stored
representation an explicit, pluggable layer:

* ``PlaneSet`` — a pytree carrying the codec id, the stored payload words,
  and per-tile metadata (column orders, constant-tile flags/values).
* ``encode`` / ``PlaneSet.decode`` — the standing contract is byte identity:
  ``decode(encode(planes)) == planes`` for every codec (pinned by
  ``tests/test_planes.py``).
* ``PlaneSet.physical`` — the dense words the crossbar *actually holds*
  (for ``col_perm`` that is the permuted layout — which is where the
  reprogramming-transition reduction physically comes from; for the
  ``const_rle`` codecs it is the reconstructed full planes).  The pool
  prices seams, counts wear, and applies fault masks on these physical
  bits, so endurance accounting stays exact under every codec; logical
  planes are recovered *after* the (possibly faulty) read via
  ``logical_from_physical``.

Codecs:

* ``raw``        — identity: payload is the canonical packed planes.
* ``const_rle``  — constant-plane run-length: a (section, column) tile whose
  ``W`` payload bytes are all equal is stored as (flag, value) and its words
  are elided from the payload (zeroed here; ``payload_bytes`` prices the
  elision).  SWS makes high-order planes constant-zero for most sections, so
  this is where the deployment weight-traffic saving concentrates.
* ``col_perm``   — per-section column permutation: along each programming
  chain, a greedy minimum-cost matching (priced through the ordinary
  ``price_pairs`` Hamming path) chooses which logical plane each physical
  bit line stores so consecutive reprograms toggle fewer cells.  A chain
  keeps its permutations only when they beat the identity layout, so encoded
  transitions never exceed raw (the ``>= 1.0x`` CI gate is structural).
* ``col_perm_rle`` — ``col_perm`` then ``const_rle`` on the permuted words
  (transition reduction and payload compression together).

Serving-side twins (``encode_operands`` / operand dicts): the serving layout
(``uint8[..., cols, ceil(K/8), N]``) gets a plane-axis permutation
(``plane_ids``) and zero-tile flags (``plane_tile_nz``) consumed by
``kernels/cim_matmul`` (tile skipping) and ``simulator`` (decode), with the
same exactness contract: encoded operands densify/serve bit-identically to
raw ones.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedule
from repro.kernels.hamming import ops as hamming_ops

CODECS = ("raw", "const_rle", "col_perm", "col_perm_rle")

# serving-side zero-tile granularity: 16 packed bytes = 128 weight rows; the
# packed kernel's skip twin (ops.cim_matmul_packed) reads two flags per
# 256-row decode chunk
OPERAND_TILE_BYTES = 16


def _check_codec(codec: str) -> None:
    if codec not in CODECS:
        raise ValueError(f"unknown plane codec {codec!r}; choose from {CODECS}")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PlaneSet:
    """One tensor's sections in a codec-defined stored representation.

    ``payload`` is ``uint8[S, W, cols]`` stored words: for the ``*_rle``
    codecs, constant tiles are elided (zeroed) from it and carried in
    (``const_mask``, ``const_val``); for ``col_perm*``, stored column ``j``
    of section ``s`` holds logical plane ``col_order[s, j]``.
    """

    codec: str  # static
    payload: jax.Array  # uint8[S, W, cols]
    col_order: jax.Array | None = None  # int32[S, cols] stored pos -> logical plane
    const_mask: jax.Array | None = None  # bool[S, cols] tile is constant
    const_val: jax.Array | None = None  # uint8[S, cols] the constant byte

    def tree_flatten(self):
        return (self.payload, self.col_order, self.const_mask, self.const_val), (self.codec,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        payload, col_order, const_mask, const_val = children
        return cls(aux[0], payload, col_order, const_mask, const_val)

    # -- the two views ------------------------------------------------------

    def physical(self) -> jax.Array:
        """Dense stored words the crossbar holds -> uint8[S, W, cols].

        The pool programs, prices, wears, and fault-masks exactly these bits.
        For ``raw``/``col_perm`` this is the payload itself (same array —
        the raw path stays bit-identical by construction); the ``*_rle``
        codecs re-broadcast their constant tiles.
        """
        if self.const_mask is None:
            return self.payload
        const = jnp.broadcast_to(self.const_val[:, None, :], self.payload.shape)
        return jnp.where(self.const_mask[:, None, :], const, self.payload)

    def decode(self) -> jax.Array:
        """Logical canonical packed planes — byte-identical to the encoder
        input for every codec (the round-trip contract)."""
        return logical_from_physical(self.physical(), self.col_order)

    # -- accounting ---------------------------------------------------------

    def compression_stats(self) -> dict[str, int | float]:
        """Stored-representation size: payload words kept, metadata bytes.

        ``payload_bytes`` counts ``W`` bytes per non-elided (section, column)
        tile; ``meta_bytes`` prices the sideband exactly (1 byte per stored
        column order entry, 1 bit per constant flag, 1 byte per constant
        value).  ``raw_bytes`` is the uncompressed ``S * W * cols``.
        """
        s, w, cols = self.payload.shape
        raw_bytes = s * w * cols
        if self.const_mask is not None:
            kept = int(np.sum(~np.asarray(self.const_mask)))
            n_const = s * cols - kept
            payload_bytes = kept * w
            meta_bytes = -(-s * cols // 8) + n_const
        else:
            payload_bytes = raw_bytes
            meta_bytes = 0
        if self.col_order is not None:
            meta_bytes += s * cols
        total = payload_bytes + meta_bytes
        return {
            "raw_bytes": raw_bytes,
            "payload_bytes": payload_bytes,
            "meta_bytes": meta_bytes,
            "total_bytes": total,
            "ratio_vs_raw": raw_bytes / max(total, 1),
        }


def logical_from_physical(physical: jax.Array, col_order: jax.Array | None) -> jax.Array:
    """Invert a column permutation on dense stored words.

    The decode direction for whatever came back from the crossbar — the
    target planes or a (possibly stucked / fault-masked) ``achieved_read``:
    masks apply to the *stored* layout first, logical recovery happens after
    the read, mirroring the hardware order of operations.
    """
    if col_order is None:
        return physical
    # col_order is a permutation per section, so argsort is its inverse:
    # logical column c lives at stored position inv[c]
    inv = jnp.argsort(col_order, axis=-1).astype(jnp.int32)
    return jnp.take_along_axis(physical, inv[:, None, :], axis=2)


# ---------------------------------------------------------------------------
# Encoders
# ---------------------------------------------------------------------------

def _const_tiles(payload: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Detect constant (section, column) tiles: all ``W`` bytes equal."""
    mask = jnp.all(payload == payload[:, :1, :], axis=1)  # bool[S, cols]
    val = payload[:, 0, :]  # uint8[S, cols]
    elided = jnp.where(mask[:, None, :], jnp.uint8(0), payload)
    return elided, mask, val


def encode(
    packed: jax.Array,
    codec: str,
    *,
    chains: list[np.ndarray] | None = None,
    pin_cols: int = 0,
) -> PlaneSet:
    """Canonical packed planes ``uint8[S, W, cols]`` -> :class:`PlaneSet`.

    ``col_perm*`` needs ``chains`` (the programming schedule) — the column
    orders are planned along them, against each section's actual
    predecessor; ``pin_cols`` keeps the lowest columns at identity (see
    :func:`plan_col_order` — required under bit stucking).
    ``decode(encode(p)) == p`` byte-for-byte for every codec.
    """
    _check_codec(codec)
    packed = jnp.asarray(packed)
    if packed.dtype != jnp.uint8 or packed.ndim != 3:
        raise ValueError(f"expected canonical uint8[S, W, cols] planes, got {packed.dtype}{packed.shape}")
    if codec == "raw":
        return PlaneSet("raw", packed)
    if codec == "const_rle":
        payload, mask, val = _const_tiles(packed)
        return PlaneSet(codec, payload, None, mask, val)
    # col_perm / col_perm_rle
    if chains is None:
        raise ValueError(f"codec {codec!r} plans column orders along chains; pass chains=")
    col_order = plan_col_order(packed, chains, pin_cols=pin_cols)
    order_dev = jnp.asarray(col_order)
    stored = jnp.take_along_axis(packed, order_dev[:, None, :], axis=2)
    if codec == "col_perm":
        return PlaneSet(codec, stored, order_dev)
    payload, mask, val = _const_tiles(stored)
    return PlaneSet(codec, payload, order_dev, mask, val)


def _greedy_assign(m: np.ndarray, pin: int = 0) -> np.ndarray:
    """Greedy minimum-cost bipartite matching on a small square cost matrix.

    Repeatedly takes the globally cheapest free (row, col) pair —
    deterministic (np.argmin takes the first minimum).  ``out[j] = b``:
    stored position ``j`` takes logical plane ``b``.  The first ``pin``
    positions are fixed to identity before matching (see
    :func:`plan_col_order`).
    """
    n = m.shape[0]
    m = m.astype(np.int64).copy()
    big = np.iinfo(np.int64).max
    out = np.full(n, -1, np.int32)
    for j in range(min(pin, n)):
        out[j] = j
        m[j, :] = big
        m[:, j] = big
    for _ in range(n - min(pin, n)):
        j, b = np.unravel_index(np.argmin(m), m.shape)
        out[j] = b
        m[j, :] = big
        m[:, b] = big
    return out


def plan_col_order(
    packed: jax.Array, chains: list[np.ndarray], *, pin_cols: int = 0
) -> np.ndarray:
    """Chain-aware per-section column orders -> host int32[S, cols].

    For every chain step the full logical-column cross-distance matrix
    ``D[a, b] = hamming(prev[:, a], cur[:, b])`` is priced in ONE batched
    ``price_pairs`` call (the same Pallas-on-TPU / popcount-elsewhere path
    every other transition count takes), then a host greedy matching walks
    each chain: stored slot ``j``'s cost of taking logical plane ``b`` is
    ``D[prev_order[j], b]``, so choices compose along the chain.  The first
    section of every chain keeps the identity order (its seam reprograms
    unknown prior pool content — nothing to match against at plan time), and
    a chain reverts wholesale to identity when its matched layout does not
    beat the raw one, which makes the encoded transition total <= raw's by
    construction for any pool state.

    ``pin_cols`` fixes the lowest ``pin_cols`` logical columns at their
    identity positions.  Bit stucking (§IV) deliberately under-programs the
    *stored* lowest-order column(s), relying on them holding the logical
    LSBs whose error is bounded; a permutation that parks a high-order
    plane there would turn that bounded LSB error into a high-order one.
    The planner pins ``stuck_cols`` whenever ``p_stuck < 1``.  The cost is
    negligible: the LSB column is ~Bernoulli(0.5) and uncorrelated, so
    matching it to anything saves essentially nothing.
    """
    packed = jnp.asarray(packed)
    s, w, cols = packed.shape
    pin_cols = min(max(int(pin_cols), 0), cols)
    order = np.tile(np.arange(cols, dtype=np.int32), (s, 1))
    prev_i, cur_i = schedule.chain_pairs(chains, include_initial=False)
    t_total = prev_i.shape[0]
    if t_total == 0:
        return order

    # D[t, a, b] = popcount(packed[prev_t][:, a] ^ packed[cur_t][:, b])
    at = jnp.moveaxis(packed[prev_i], -1, 1)  # [T, cols, W]
    bt = jnp.moveaxis(packed[cur_i], -1, 1)
    a_full = jnp.broadcast_to(at[:, :, None, :], (t_total, cols, cols, w))
    b_full = jnp.broadcast_to(bt[:, None, :, :], (t_total, cols, cols, w))
    d = np.asarray(
        hamming_ops.price_pairs(
            a_full.reshape(t_total * cols * cols, w, 1),
            b_full.reshape(t_total * cols * cols, w, 1),
        ),
        np.int64,
    ).reshape(t_total, cols, cols)

    idx = np.arange(cols)
    t = 0
    for ch in chains:
        ch = np.asarray(ch, dtype=np.int64)
        prev_order = idx.copy()
        raw_cost = 0
        new_cost = 0
        chain_orders: list[np.ndarray] = []
        for _ in range(len(ch) - 1):
            dm = d[t]
            t += 1
            raw_cost += int(dm[idx, idx].sum())
            m = dm[prev_order, :]  # m[j, b] = D[prev_order[j], b]
            cur_order = _greedy_assign(m, pin_cols)
            new_cost += int(m[idx, cur_order].sum())
            chain_orders.append(cur_order)
            prev_order = cur_order
        if new_cost < raw_cost:
            for step, co in enumerate(chain_orders):
                order[ch[step + 1]] = co
    return order


# ---------------------------------------------------------------------------
# Serving-operand twins (simulator.packed_operands layout)
# ---------------------------------------------------------------------------

def _tile_nz(planes: jax.Array) -> jax.Array:
    """Zero-tile flags for serving planes ``uint8[..., cols, Kw, N]``.

    One flag per (plane, 128-row K block): ``uint8[..., cols, ceil(Kw/16)]``,
    1 iff any byte in the tile (across all N) is nonzero.  Matches the packed
    kernel's (plane, K-block) work unit, so a 0 flag is a skippable tile.
    """
    kw = planes.shape[-2]
    pad = (-kw) % OPERAND_TILE_BYTES
    if pad:
        planes = jnp.pad(planes, [(0, 0)] * (planes.ndim - 2) + [(0, pad), (0, 0)])
    shaped = planes.reshape(
        planes.shape[:-2] + (-1, OPERAND_TILE_BYTES) + planes.shape[-1:]
    )
    return jnp.any(shaped != 0, axis=(-2, -1)).astype(jnp.uint8)


def encode_operands(op: dict[str, jax.Array], codec: str) -> dict[str, jax.Array]:
    """Apply a codec to a packed serving operand dict (exactness-preserving).

    * ``col_perm*`` reorders the plane axis by descending bit density and
      records ``plane_ids`` (stored plane ``p`` holds logical plane
      ``plane_ids[p]``); consumers weight plane ``p`` by ``2**plane_ids[p]``,
      so decode is exact.
    * ``*_rle`` adds ``plane_tile_nz`` zero-tile flags — the payload needs no
      rewrite (zero tiles are already zero bytes); the flags drive the
      kernel's tile skipping and the roofline's compressed-traffic pricing.

    Must run *before* ``nonideal.perturb_operands``: fault masks attach to
    the stored layout, and logical decode happens after the masked read.
    """
    _check_codec(codec)
    if codec == "raw":
        return op
    if "planes_packed" not in op:
        raise ValueError("encode_operands expects packed serving operands")
    out = dict(op)
    planes = op["planes_packed"]  # [..., cols, Kw, N]
    if codec in ("col_perm", "col_perm_rle"):
        ones = jnp.sum(
            jax.lax.population_count(planes).astype(jnp.int32), axis=(-2, -1)
        )  # [..., cols]
        plane_ids = jnp.argsort(-ones, axis=-1, stable=True).astype(jnp.int32)
        planes = jnp.take_along_axis(planes, plane_ids[..., :, None, None], axis=-3)
        out["plane_ids"] = plane_ids
        out["planes_packed"] = planes
    if codec in ("const_rle", "col_perm_rle"):
        out["plane_tile_nz"] = _tile_nz(planes)
    return out


def operand_payload_bytes(op: dict[str, jax.Array]) -> dict[str, int]:
    """Weight bytes a decode step reads from an encoded operand dict.

    Zero tiles flagged in ``plane_tile_nz`` are not read (their contribution
    is identically zero); the sign mask and the codec sideband are.  Without
    flags this reduces to the packed representation's byte count.
    """
    planes = op["planes_packed"]
    n = planes.shape[-1]
    sign_bytes = int(np.prod(op["sign_packed"].shape))
    meta = 0
    if "plane_ids" in op:
        meta += int(np.prod(op["plane_ids"].shape))
    if "plane_tile_nz" in op:
        flags = np.asarray(op["plane_tile_nz"])
        meta += flags.size
        # the last K-tile may be ragged: count the bytes it actually holds
        kw = planes.shape[-2]
        n_tiles = flags.shape[-1]
        tile_bytes = np.minimum(
            OPERAND_TILE_BYTES, kw - OPERAND_TILE_BYTES * np.arange(n_tiles)
        )
        plane_bytes = int((flags * tile_bytes).sum()) * n
    else:
        plane_bytes = int(np.prod(planes.shape))
    return {
        "plane_bytes": plane_bytes,
        "sign_bytes": sign_bytes,
        "meta_bytes": meta,
        "total_bytes": plane_bytes + sign_bytes + meta,
    }
