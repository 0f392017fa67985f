"""Shared int8 block quantizer: the KV wire codec and the gradient
all-reduce's quantization core (DESIGN.md §14).

One scheme, two call sites:

- ``distributed/compression.py`` quantizes gradient blocks on device
  (jax) for the int8 all-reduce — it imports ``_pad_blocks`` and
  ``block_scale`` from here so the two tiers can never drift.
- The paged KV offload path quantizes page payloads on host (numpy)
  before they enter the DRAM tier: ``KVWireCodec`` encodes a page's
  ``[2, L, page, Hkv*hd]`` host stack to ``(int8 payload, fp32 block
  scales)`` at offload time and decodes it as the reload chunk lands.

Scheme (per BLOCK-element block):

  scale = max(|x|, eps) / 127        q = clip(round(x / scale), ±127)

The epsilon guards the *max*, not the quotient: adding it after the
division (the old compression.py bug) inflated every scale so the
max-magnitude element no longer mapped to ±127 and the worst-case
round-trip error exceeded scale/2. With the guard on the max, the
error bound  |decode(encode(x)) - x| <= scale / 2  is tight, exact
zeros survive the round trip exactly (round(0) * scale == 0), and the
KV quality gate's tolerances (tests/test_quality_gate.py) hold.

Wire size: BLOCK int8 lanes + one fp32 scale per block, so an int8
page costs ``(1 + 4/BLOCK)`` bytes per element against ``itemsize``
for the native dtype — ``wire_scale`` ~ 0.254 for fp32 KV. The modeled
PCIe channel multiplies by this factor (``TransferChannel.wire_scale``)
so chunk sizing, reload stall accounting, and ``reload_overlap_frac``
all see the compressed size.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Union

import numpy as np

BLOCK = 256
EPS = 1e-12

KV_WIRE_FORMATS = ("fp32", "int8")


# ---------------------------------------------------------------- jax side
def _pad_blocks(flat):
    """Pad a flat jax array to a BLOCK multiple and reshape to
    [nb, BLOCK]. Returns (blocks, pad). Pad lanes are zeros: they can
    never raise a block's max, and decoders slice them off by the
    original size."""
    import jax.numpy as jnp
    pad = (-flat.size) % BLOCK
    return jnp.pad(flat, (0, pad)).reshape(-1, BLOCK), pad


def block_scale(maxabs, eps: float = EPS):
    """Per-block scale from per-block max magnitudes (jax). The epsilon
    guards the max (an all-zero block would otherwise divide by zero);
    it must NOT be added after the division — that inflates every
    scale and loosens the round-trip error bound."""
    import jax.numpy as jnp
    return jnp.maximum(maxabs, eps) / 127.0


# --------------------------------------------------------------- host side
@dataclass
class QuantizedPage:
    """One KV page's host copy in int8 wire format: ``q`` [nb, BLOCK]
    int8 payload, ``scales`` [nb] fp32 shared block scales, plus the
    original shape/dtype for decode. Opaque to the pool's host store —
    conservation, cancellation, and migration handoff treat it exactly
    like the fp32 ndarray it replaces."""
    q: np.ndarray
    scales: np.ndarray
    shape: tuple
    dtype: np.dtype

    @property
    def nbytes(self) -> int:
        return self.q.nbytes + self.scales.nbytes


def encode_page(host: np.ndarray, eps: float = EPS) -> QuantizedPage:
    """int8-encode a host array with BLOCK-granular fp32 scales."""
    flat = np.asarray(host, np.float32).reshape(-1)
    pad = (-flat.size) % BLOCK
    if pad:
        flat = np.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    scales = np.maximum(np.abs(blocks).max(axis=1), eps) \
        .astype(np.float32) / 127.0
    q = np.clip(np.rint(blocks / scales[:, None]), -127, 127) \
        .astype(np.int8)
    return QuantizedPage(q=q, scales=scales, shape=tuple(host.shape),
                         dtype=np.dtype(host.dtype))


def decode_page(page: QuantizedPage) -> np.ndarray:
    """Inverse of ``encode_page`` (up to <= scale/2 per element)."""
    flat = page.q.astype(np.float32) * page.scales[:, None]
    n = int(np.prod(page.shape))
    return flat.reshape(-1)[:n].reshape(page.shape).astype(page.dtype)


def decode_host(obj: Union[np.ndarray, QuantizedPage]) -> np.ndarray:
    """Decode a host-store entry whatever its wire format: pass fp32
    ndarrays through untouched (bit-exact), dequantize QuantizedPage.
    The pool's synchronous reload fallback and the engine's chunk io
    both route through this, so a host store can even hold mixed
    formats (e.g. pages adopted from a migration)."""
    if isinstance(obj, QuantizedPage):
        return decode_page(obj)
    return obj


class KVWireCodec:
    """The offload path's wire-format choice, threaded from
    ``PagedRealtimeEngine(kv_quant=...)`` down to the pool and the
    modeled channel. ``fp32`` is the identity codec (the bit-exact
    differential control — 'fp32' meaning the KV store's native dtype,
    untouched); ``int8`` block-quantizes every host copy."""

    def __init__(self, fmt: str = "fp32"):
        if fmt not in KV_WIRE_FORMATS:
            raise ValueError(
                f"kv_quant must be one of {KV_WIRE_FORMATS}, got {fmt!r}")
        self.fmt = fmt

    def encode(self, host: np.ndarray):
        if self.fmt == "fp32":
            return host
        return encode_page(host)

    def decode(self, obj) -> np.ndarray:
        return decode_host(obj)

    def wire_scale(self, dtype) -> float:
        """Wire bytes per logical byte: the factor the modeled PCIe
        channel multiplies into ``transfer_time`` so every consumer
        (chunk sizing, preload admission, stall settlement, fleet
        migration) prices the compressed payload. Includes the fp32
        scale overhead (4 bytes per BLOCK elements)."""
        if self.fmt == "fp32":
            return 1.0
        return (1.0 + 4.0 / BLOCK) / np.dtype(dtype).itemsize
