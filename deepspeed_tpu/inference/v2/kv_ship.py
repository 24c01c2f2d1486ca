"""Live KV-page shipping between serving replicas (disaggregated prefill).

The PR-7 resharding planner moves CHECKPOINT tensors between mesh shapes by
dropping to a canonical layout and re-chunking for the target; this module
does the same for LIVE paged-KV state: a sequence's cache rows are exported
in canonical row-space ``[num_layers, n_tokens, 2*kv_heads, head_dim]``
(block tables dissolved), shipped, and re-chunked into the RECEIVING
engine's page geometry — so a prefill-shaped replica (big ``block_size``,
deep token budget) can hand a prompt's KV to a decode replica with a
different pool layout and the stream continues bit-exactly.

Wire formats
  * ``fp32`` — raw little-endian float32 rows; bit-exact by construction.
  * ``int8`` — the PR-9 fused-wire kernels (``ops/quantizer``
    ``quant_pack_wire``/``unpack_dequant_wire``, the same scale/round math
    the quantized collectives exchange): group-wise max-abs scaling, one
    byte per value plus one f32 scale per group.  Error is BOUNDED by
    half a quantization step per element (``|x - dq| <= scale/2``), which
    :func:`int8_error_bound` exposes and the wire tests assert.

Framing: ``DSKV1`` magic + 4-byte big-endian header length + JSON header +
payload bytes.  :func:`to_wire`/:func:`from_wire` are the only
(de)serializers; HTTP carriers base64 the frame into JSON bodies.

The export is a READ — shared (prefix-cache) pages serialize like any
other row source, and the exporting sequence keeps its blocks.  The import
is a fresh allocation on the target: the new sequence owns its pages at
refcount 1, so later appends never need copy-on-write.
"""
from __future__ import annotations

import base64
import dataclasses
import json
import struct
from typing import Dict, List, Optional, Tuple

import numpy as np

MAGIC = b"DSKV1"
WIRE_FORMATS = ("fp32", "int8")
INT8_GROUP = 256


@dataclasses.dataclass
class KVShipment:
    """Canonical-row-space snapshot of one sequence's cached prefix."""

    tokens: List[int]             # attested tokens; rows == len(tokens)
    num_layers: int
    num_kv_heads: int
    head_dim: int
    src_block_size: int           # informational: exporter's page geometry
    wire: str                     # "fp32" | "int8"
    rows: np.ndarray              # [L, n, 2*KV, HD] float32

    @property
    def n_tokens(self) -> int:
        return len(self.tokens)


def _refuse_latent(engine, what: str) -> None:
    """A shipment is canonical K/V rows ``[L, n, 2*KV, hd]``; a latent (MLA)
    page has no such shape, and a reshaped copy would be silently wrong."""
    if getattr(engine, "latent_kv", False):
        raise NotImplementedError(
            f"{what} is not supported with latent (MLA) pages: the shipment "
            f"format holds K/V rows per head")
    if getattr(engine, "index_kv", None) is not None:
        raise NotImplementedError(
            f"{what} is not supported with index keys beside the K/V rows "
            f"(sparse attention): the shipment format holds K/V rows and "
            f"not the keys the indexer scores")
    if getattr(engine, "state_pool", None) is not None:
        raise NotImplementedError(
            f"{what} is not supported with recurrent state: a shipment "
            f"holds cached rows and no state to continue from — state "
            f"snapshots are ROADMAP R5")


def export_kv(engine, uid: int, tokens: List[int],
              n_tokens: Optional[int] = None) -> KVShipment:
    """Snapshot the first ``n_tokens`` cached rows of ``uid`` (default:
    everything seen) into canonical row space.  ``tokens`` are the ids
    whose KV those rows hold — the importer re-attests them against its
    own request's prompt, the cheap insurance against grafting the wrong
    conversation's cache."""
    import jax.numpy as jnp

    _refuse_latent(engine, "export_kv (prefill_only requests)")
    seq = engine.state_manager.get_sequence(uid)
    assert seq is not None, f"export of unknown uid {uid}"
    n = seq.seen_tokens if n_tokens is None else min(int(n_tokens),
                                                     seq.seen_tokens)
    assert len(tokens) >= n, \
        f"attested tokens ({len(tokens)}) shorter than rows ({n})"
    bs = engine.config.block_size
    n_pages = -(-n // bs)
    assert len(seq.blocks) >= n_pages, "block table shorter than rows"
    nb = engine.kv.config.num_blocks
    # one gather for all layers: [L * n_pages] physical page ids
    phys = np.asarray([b + layer * nb
                       for layer in range(engine.family.page_layers)
                       for b in seq.blocks[:n_pages]], np.int64)
    pages = np.asarray(engine.kv.pages[jnp.asarray(phys)], np.float32)
    row = engine.family.row
    # one head a row, whatever form the pool stores a token in
    rows = pages.reshape(engine.family.page_layers, n_pages * bs,
                         2 * row.stored, row.head_dim)[:, :n]
    if row.stored != row.num_kv_heads:
        # canonical rows carry the model's heads, not the pool's padding
        kv = row.num_kv_heads
        rows = np.concatenate([rows[:, :, :kv],
                               rows[:, :, row.stored:row.stored + kv]], axis=2)
    return KVShipment(tokens=[int(t) for t in tokens[:n]],
                      num_layers=engine.family.page_layers,
                      num_kv_heads=row.num_kv_heads, head_dim=row.head_dim,
                      src_block_size=bs, wire="fp32", rows=rows)


def import_kv(engine, shipment: KVShipment, uid: int) -> bool:
    """Graft a shipment into ``engine`` as a fresh sequence ``uid`` —
    re-chunking canonical rows into the target's page geometry.  Returns
    False on transient block exhaustion (the caller's backpressure /
    preemption machinery owns the retry); raises on a geometry mismatch
    (wrong model), which no retry can fix."""
    import jax.numpy as jnp

    _refuse_latent(engine, "import_kv (kv_import requests)")
    row = engine.family.row
    if (shipment.num_layers != engine.family.page_layers
            or shipment.num_kv_heads != row.num_kv_heads
            or shipment.head_dim != row.head_dim):
        raise ValueError(
            f"KV shipment geometry mismatch: shipment "
            f"L{shipment.num_layers}/kv{shipment.num_kv_heads}"
            f"/hd{shipment.head_dim} vs engine L{engine.family.page_layers}"
            f"/kv{row.num_kv_heads}/hd{row.head_dim}")
    n = shipment.n_tokens
    sm = engine.state_manager
    seq = sm.get_or_create_sequence(uid)
    assert not seq.blocks and seq.seen_tokens == 0, \
        f"KV import into a non-fresh sequence uid={uid}"
    if not sm.maybe_allocate_kv(seq, n):
        sm._seqs.pop(uid, None)        # roll back the empty descriptor
        return False
    bs = engine.config.block_size
    n_pages = -(-n // bs)
    pad = n_pages * bs - n
    rows = shipment.rows.astype(np.float32)
    if row.stored != row.num_kv_heads:
        kv, extra = row.num_kv_heads, row.stored - row.num_kv_heads
        zeros = np.zeros(rows.shape[:2] + (extra, row.head_dim), np.float32)
        rows = np.concatenate([rows[:, :, :kv], zeros, rows[:, :, kv:], zeros],
                              axis=2)
    if pad:
        rows = np.pad(rows, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pages = rows.reshape(shipment.num_layers, n_pages, bs,
                         *row.token_shape)
    nb = engine.kv.config.num_blocks
    phys = np.asarray([b + layer * nb
                       for layer in range(shipment.num_layers)
                       for b in seq.blocks[:n_pages]], np.int64)
    flat = pages.reshape(shipment.num_layers * n_pages, bs,
                         *row.token_shape)
    engine.kv.update(engine.kv.pages.at[jnp.asarray(phys)].set(
        jnp.asarray(flat, engine.kv.pages.dtype)))
    seq.seen_tokens = n
    seq.input_ids = list(shipment.tokens)
    engine._decode_state = None
    return True


# --------------------------------------------------------------------- #
# Wire (de)serialization
# --------------------------------------------------------------------- #
def int8_error_bound(scales: np.ndarray, group_size: int,
                     n: int) -> np.ndarray:
    """Per-element absolute error bound of the int8 wire: half a
    quantization step, expanded from per-group scales to the first ``n``
    flat elements."""
    per_elem = np.repeat(np.asarray(scales, np.float32).reshape(-1),
                         group_size)[:n]
    return per_elem * 0.5 + 1e-7


def to_wire(shipment: KVShipment, wire: str = "fp32") -> bytes:
    """Serialize for transport.  ``int8`` runs the PR-9 fused-wire
    quantize+pack kernel over the rows; the header carries the per-group
    scales so the receiver's dequant is self-contained."""
    if wire not in WIRE_FORMATS:
        raise ValueError(f"wire must be one of {WIRE_FORMATS}, got {wire!r}")
    header: Dict = {
        "tokens": shipment.tokens,
        "num_layers": shipment.num_layers,
        "num_kv_heads": shipment.num_kv_heads,
        "head_dim": shipment.head_dim,
        "src_block_size": shipment.src_block_size,
        "wire": wire,
        "shape": list(shipment.rows.shape),
    }
    if wire == "fp32":
        payload = shipment.rows.astype("<f4").tobytes()
    else:
        from ...ops.quantizer.quantizer import quant_pack_wire

        w, scales = quant_pack_wire(shipment.rows, bits=8,
                                    group_size=INT8_GROUP)
        w = np.asarray(w, np.int8)
        scales = np.asarray(scales, np.float32)
        header["group_size"] = INT8_GROUP
        header["groups"] = int(w.shape[0])
        payload = w.tobytes() + scales.astype("<f4").tobytes()
    hdr = json.dumps(header, sort_keys=True).encode()
    return MAGIC + struct.pack(">I", len(hdr)) + hdr + payload


def from_wire(data: bytes) -> KVShipment:
    if data[:len(MAGIC)] != MAGIC:
        raise ValueError("not a DSKV1 frame")
    (hlen,) = struct.unpack(">I", data[len(MAGIC):len(MAGIC) + 4])
    off = len(MAGIC) + 4
    header = json.loads(data[off:off + hlen])
    payload = data[off + hlen:]
    shape = tuple(header["shape"])
    n_elems = int(np.prod(shape))
    if header["wire"] == "fp32":
        rows = np.frombuffer(payload, "<f4", count=n_elems).reshape(shape)
    else:
        from ...ops.quantizer.quantizer import unpack_dequant_wire

        import jax.numpy as jnp

        groups = header["groups"]
        gs = header["group_size"]
        w = np.frombuffer(payload, np.int8,
                          count=groups * gs).reshape(groups, gs)
        scales = np.frombuffer(payload[groups * gs:], "<f4",
                               count=groups).reshape(groups, 1)
        rows = np.asarray(unpack_dequant_wire(
            jnp.asarray(w), jnp.asarray(scales), bits=8, shape=shape,
            dtype=jnp.float32))
    return KVShipment(tokens=[int(t) for t in header["tokens"]],
                      num_layers=int(header["num_layers"]),
                      num_kv_heads=int(header["num_kv_heads"]),
                      head_dim=int(header["head_dim"]),
                      src_block_size=int(header["src_block_size"]),
                      wire=str(header["wire"]), rows=rows)


def to_b64(shipment: KVShipment, wire: str = "fp32") -> str:
    """Frame + base64, for embedding in JSON HTTP bodies."""
    return base64.b64encode(to_wire(shipment, wire=wire)).decode()


def from_b64(data: str) -> KVShipment:
    return from_wire(base64.b64decode(data))
