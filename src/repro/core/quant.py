"""Fixed-point quantization schemes (paper §III, §VIII-B).

The paper uses symmetric fixed-point quantization with notation WxAy
(weight word length x, activation word length y). Quantization is applied
*vector-wise* ("quantization is applied vector-wise in the produced matrix"
— §VIII-B) which on a (K, N) weight matrix means one scale per output
column (per-channel), and on the SVD factors one scale per rank-column /
rank-row.

On TPU there is no native int4/int6 datapath, but HBM residency does not
have to pay for the carrier: W4 tensors are *packed* two nibbles per int8
byte in HBM (`pack_weights`) and unpacked on-chip, inside the Pallas
kernels, right before the int8xint8->int32 MXU dot. W6 has no byte-aligned
packing (4 codes per 3 bytes straddles lanes) and stays int8-carrier
resident — and is *accounted* as 8 bits, not 6: `storage_bits()` reports
the bytes the device arrays actually occupy, never a pretended packed
size. See core/compress.py for whole-model accounting.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

Array = jax.Array


def qmax(wl: int) -> int:
    """Largest magnitude representable by a symmetric signed `wl`-bit code."""
    if wl < 2:
        raise ValueError(f"word length must be >= 2, got {wl}")
    return 2 ** (wl - 1) - 1


@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """A symmetric per-axis quantized tensor.

    values : integer codes. Carrier layout: one int8 per code
             (|v| <= qmax(wl)). Packed layout (`packed=True`, wl == 4
             only): two nibble codes per int8 byte along the LAST axis,
             so `values.shape[-1]` is half the logical width.
    scale  : fp32 scale, broadcastable against the *logical* values along
             `axis`
    wl     : word length in bits (4, 6, 8) — the code range
    axis   : axis along which scales are shared (the reduction axis of the
             matmul this tensor feeds); scale shape has 1 there.
    packed : True when `values` holds the packed-nibble HBM layout
    act_wl : word length the activations feeding this weight's matmul are
             quantized to at runtime (the plan's WxAy "Ay"); 8 keeps the
             historical A8 behavior bit-identical.

    `wl`, `axis`, `packed`, `act_wl` are pytree aux data: static under
    jit, so kernels specialize on the layout and clamp range, and a plan
    with a different act_wl or packing retraces instead of reusing a
    stale compilation.
    """

    values: Array
    scale: Array
    wl: int
    axis: int
    packed: bool = False
    act_wl: int = 8

    @property
    def shape(self):
        """LOGICAL shape (unpacked), regardless of residency layout."""
        s = self.values.shape
        if self.packed:
            return (*s[:-1], s[-1] * 2)
        return s

    def dequant(self) -> Array:
        v = unpack_int4(self.values) if self.packed else self.values
        return v.astype(jnp.float32) * self.scale

    def storage_bits(self) -> int:
        """HBM storage cost in bits of the arrays as they are actually
        resident: 8 bits per stored byte (so wl per logical code when
        packed, a full 8 for any int8-carrier tensor — including W4/W6
        that was *not* packed) plus fp32 scales. Honest by construction:
        it counts device bytes, not the word length we wish we stored."""
        n = 1
        for d in self.values.shape:
            n *= int(d)
        ns = 1
        for d in self.scale.shape:
            ns *= int(d)
        return n * 8 + ns * 32


jax.tree_util.register_pytree_with_keys(
    QuantizedTensor,
    lambda q: ((("values", q.values), ("scale", q.scale)),
               (q.wl, q.axis, q.packed, q.act_wl)),
    lambda aux, ch: QuantizedTensor(ch[0], ch[1], *aux),
)


@partial(jax.jit, static_argnames=("wl", "axis"))
def quantize(x: Array, wl: int, axis: int = 0) -> QuantizedTensor:
    """Symmetric per-vector quantization of `x` along `axis`.

    `axis` is the reduction axis: scales are shared along it (one scale per
    remaining index), matching the paper's vector-wise scheme.
    """
    m = qmax(wl)
    absmax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / m, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -m, m).astype(jnp.int8)
    return QuantizedTensor(q, scale, wl, axis)


def dequantize(q: QuantizedTensor) -> Array:
    return q.dequant()


@partial(jax.jit, static_argnames=("wl", "axis"))
def fake_quant(x: Array, wl: int, axis: int = 0) -> Array:
    """Quantize-dequantize in one go (used for activation quantization and
    for emulating the quantized model in fp math)."""
    m = qmax(wl)
    absmax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / m, 1.0)
    return jnp.clip(jnp.round(x / scale), -m, m) * scale


@partial(jax.jit, static_argnames=("w_wl", "a_wl"))
def quant_linear_ref(x: Array, w: Array, w_wl: int, a_wl: int) -> Array:
    """Reference WxAy linear layer: y = Qa(x) @ Qw(w).

    Weight scales are per output channel (axis=0 of the (K, N) matrix is the
    reduction axis); activation scales per token row.
    """
    qw = quantize(w, w_wl, axis=0)
    xq = fake_quant(x, a_wl, axis=-1)
    return xq @ qw.dequant()


# Packed-W4 byte layout, by tiles of PACK_TILE columns along the last
# axis: byte j of a tile holds column j in its low nibble and column
# j + PACK_TILE/2 in its high nibble. A kernel then unpacks each
# 128-byte half-tile with two shifts and puts the halves side by side on
# 128-lane boundaries — no lane interleave. Checkpoints record this name
# and refuse packed arrays written under any other layout.
PACK_TILE = 256
PACK_LAYOUT = "int4-halves-256"


def pack_int4(codes: Array) -> Array:
    """Pack int8-carried int4 codes into bytes (two nibbles per byte).

    This IS the HBM layout for packed W4 weights (see PACK_TILE): within
    each tile of width t, column j goes to the low nibble and column
    j + t/2 to the high nibble of the tile's byte j. Only whole
    PACK_TILE tiles are what the kernels read (`packed_pad_ok`); a
    narrower (even) last tile round-trips through `unpack_int4` alone.
    The last dim must be even. Values must be in [-8, 7].
    """
    *lead, n = codes.shape
    if n % 2:
        raise ValueError(
            f"pack_int4 needs an even last dim, got shape {codes.shape}")
    full = n - n % PACK_TILE
    tiles = codes[..., :full].reshape(*lead, full // PACK_TILE, 2,
                                      PACK_TILE // 2)
    tail = codes[..., full:]
    lo = jnp.concatenate([tiles[..., 0, :].reshape(*lead, full // 2),
                          tail[..., :tail.shape[-1] // 2]], axis=-1)
    hi = jnp.concatenate([tiles[..., 1, :].reshape(*lead, full // 2),
                          tail[..., tail.shape[-1] // 2:]], axis=-1)
    return ((lo & 0x0F) | ((hi & 0x0F) << 4)).astype(jnp.int8)


def unpack_int4(packed: Array) -> Array:
    """Inverse of pack_int4 (sign-extends each nibble)."""
    *lead, c = packed.shape
    p32 = packed.astype(jnp.int32)
    lo = ((p32 << 28) >> 28).astype(jnp.int8)     # sign-extended low nibble
    hi = ((p32 << 24) >> 28).astype(jnp.int8)     # sign-extended high nibble
    half = PACK_TILE // 2
    full = c - c % half                           # bytes of whole tiles
    tiles = jnp.stack([lo[..., :full].reshape(*lead, full // half, half),
                       hi[..., :full].reshape(*lead, full // half, half)],
                      axis=-2)
    return jnp.concatenate([tiles.reshape(*lead, 2 * full),
                            lo[..., full:], hi[..., full:]], axis=-1)


def packed_pad_ok(dim: int) -> bool:
    """Whether a `dim`-wide axis packs into the layout the Pallas kernels
    read: whole PACK_TILE tiles. Padding then adds whole zero tiles,
    which unpack to zero codes, and every packed block is 128-lane
    aligned. Other widths stay int8 carriers — `packable` gates on this,
    so the decision is made ONCE at pack time, not paid per dispatch."""
    return dim % PACK_TILE == 0


def packable(q: QuantizedTensor) -> bool:
    """True when `q` can move to the packed-nibble layout: W4 codes (the
    only word length whose packing is byte-aligned) whose last dim is
    whole pack tiles (`packed_pad_ok`), not already packed."""
    return (not q.packed and q.wl == 4
            and packed_pad_ok(int(q.values.shape[-1])))


def pack_weights(q: QuantizedTensor) -> QuantizedTensor:
    """Move a W4 tensor to the packed HBM-resident layout (exact: the
    codes are unchanged, only the byte layout differs). Non-packable
    tensors (W6/W8, a last dim that is not whole pack tiles) are returned
    as-is — they stay int8 carriers and `storage_bits()` charges them
    the full 8 bits."""
    if not packable(q):
        return q
    return dataclasses.replace(q, values=pack_int4(q.values), packed=True)


def unpack_weights(q: QuantizedTensor) -> QuantizedTensor:
    """Inverse of pack_weights: back to the int8-carrier layout."""
    if not q.packed:
        return q
    return dataclasses.replace(q, values=unpack_int4(q.values), packed=False)
