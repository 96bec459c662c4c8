"""LC-style composable pipeline API (DESIGN.md §7, value stages §9).

The paper's LC framework is a *chain of interchangeable components* — a
quantizer followed by lossless stages.  This module exposes that chain as
one object instead of forked per-combination surfaces: a `Pipeline`
parsed from a spec string like

    "delta|rel:1e-3|pack:8|zero|narrow"

is any number of value-domain predictor stages (`core.predict`, applied
closed-loop around the quantizer — DESIGN.md §9), a quantizer stage, a
bit-pack stage, and any number of registered lossless *word stages*,
each transforming the packed uint32 word stream
exactly and reversibly.  Encoding produces one `Encoded` wire container
(final payload plane + per-stage header planes + transmitted lengths +
the capped exact-outlier table); `Pipeline.wire_bits` counts exactly the
transmitted prefix — never capacity padding — so the accounting matches
the pre-pipeline `EncodedPacked.wire_bits` / `EncodedLC.wire_bits` bit
for bit on the chains both can express.

Stage contract (`WordStage`): pure jit-safe pytree functions with STATIC
capacities —

    capacity_words(n_in)        static output capacity for an n_in stream
    header_words(n_in)          static stored header-plane size (0 = none)
    header_content_bits(n_in)   transmitted header bits (pad excluded)
    transmits_len               True if the output length is data-dependent
    encode_words(words, n_in)   -> (header, out[capacity], out_len)
    decode_words(header, payload, n_in) -> words[n_in]   (exact inverse)

Registered stages (see STAGES / DESIGN.md §7):

    zero, narrow  — the §6 chunked coder (`core.codec.encode_words_lc`)
    shuffle[:w]   — zigzag sign-fold + byte-plane shuffle
                    (`core.codec.shuffle_words`); w defaults to the pack
                    width
    ent           — static canonical entropy coder over surviving
                    chunks, codebook in the header plane
                    (`core.codec.encode_words_ent`)

Kernel dispatch: known chains map onto the existing fused Pallas kernels
(`kernels/pack.py`, `kernels/lossless.py`), anything else runs the jit
reference — bit-identical either way (the kernels are bit-exact twins by
test), so the §1 guarantee is untouched by dispatch.

    chain                         fused kernel
    quant|pack                    kernels.pack.encode_packed
    quant|pack|zero or |narrow    kernels.lossless.encode_packed_lc
    pred|...                      jit reference (open slot, DESIGN.md §9)
    anything else                 jit reference (core.codec)

`kernels=None` (auto) uses the fused path only on a real TPU backend;
tests force it with `kernels=True, interpret=True`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import audit as A
from . import codec as C
from . import predict as P
from .config import QuantizerConfig

_QUANT_MODES = ("abs", "rel", "noa")
_CAP_DEFAULT = 0.125          # QuantizerConfig.outlier_cap_frac default

# The two-domain spec grammar (DESIGN.md §9): value-domain pred stages
# lead, then the quantizer, the packer, and word-domain stages.
GRAMMAR = ('pipeline = { pred-stage "|" } quant:<eb> "|" pack:<bits> '
           '{ "|" word-stage }')


class Encoded(NamedTuple):
    """The one wire container every pipeline produces.

    `payload` is the FINAL word plane, padded to static capacity when any
    stage is length-variable; `payload_len` is the transmitted word count
    (a constant for static chains).  `headers` holds one stored header
    plane per word stage, in chain order (shape (0,) for headerless
    stages), so gathers and vmaps stay structurally uniform.  The outlier
    table and sign plane are exactly the §4 ones — no stage may touch
    them.  Wire accounting lives on the Pipeline (`Pipeline.wire_bits`),
    which knows each stage's transmitted header content.  `checksum` is
    the OPT-IN §12 integrity digest (encode with integrity=True): an
    extra aux plane over the transmitted fields, never inside them, so
    checksum-free wires stay bit-identical to pre-§12 encodes.
    """
    payload: jnp.ndarray          # uint32[capacity] — final word plane
    payload_len: jnp.ndarray      # int32 scalar — words a transport moves
    headers: tuple                # per-stage uint32 header planes
    out_idx: jnp.ndarray          # int32[K], n = "empty slot"
    out_payload: jnp.ndarray      # uint32[K] — original IEEE bits
    n_outliers: jnp.ndarray       # int32 scalar
    overflow: jnp.ndarray         # bool scalar (bound NOT met when True)
    sign_words: jnp.ndarray | None  # uint32 (REL only)
    eb: jnp.ndarray | None        # traced scalar bound
    checksum: jnp.ndarray | None = None  # uint32 scalar (§12, integrity=True)


def _fmt(v: float) -> str:
    """Canonical float printing for specs (shortest roundtrip repr)."""
    return repr(float(v))


# ---------------------------------------------------------------- stages --

@dataclasses.dataclass(frozen=True)
class QuantStage:
    """Quantizer front end: mode + error bound (+ outlier-cap fraction)."""
    mode: str = "abs"
    eb: float = 1e-3
    cap: float = _CAP_DEFAULT
    dtype: str = "float32"

    def spec(self) -> str:
        s = f"{self.mode}:{_fmt(self.eb)}"
        if self.cap != _CAP_DEFAULT:
            s += f":cap={_fmt(self.cap)}"
        if self.dtype != "float32":
            s += f":dtype={self.dtype}"
        return s


@dataclasses.dataclass(frozen=True)
class PackStage:
    """Bit-pack stage: bins -> uint32 lane words at `bits`/value (§4)."""
    bits: int = 16

    def spec(self) -> str:
        return f"pack:{self.bits}"


@dataclasses.dataclass(frozen=True)
class ChunkStage:
    """The §6 chunked zero/narrow coder as a word stage."""
    mode: str = "narrow"          # 'zero' | 'narrow'
    transmits_len = True

    def capacity_words(self, n_in: int) -> int:
        return C.lc_chunk_count(n_in) * C.LC_CHUNK

    def header_words(self, n_in: int) -> int:
        return C.lc_header_words(n_in)

    def header_content_bits(self, n_in: int) -> int:
        return 32 * C.lc_header_content_words(C.lc_chunk_count(n_in))

    def encode_words(self, words, n_in: int):
        return C.encode_words_lc(words, self.mode)

    def decode_words(self, header, payload, n_in: int):
        return C.decode_words_lc(header, payload, n_in)

    def spec(self) -> str:
        return self.mode


@dataclasses.dataclass(frozen=True)
class EntStage:
    """Static canonical entropy coder over surviving 512-word chunks
    (codec.encode_words_ent, DESIGN.md §7): a cuSZ-style codebook built
    from the byte histogram of the non-zero chunks rides in the header
    plane (4-bit canonical code lengths + 2-bit chunk modes + 16-bit
    chunk bit lengths); each surviving chunk encodes independently as a
    variable-length bitstream with a verbatim escape, so the stage never
    costs more than its header content.  Length-variable: the payload is
    carried padded to capacity with the transmitted word count (§6
    pattern)."""
    transmits_len = True

    def capacity_words(self, n_in: int) -> int:
        return C.lc_chunk_count(n_in) * C.LC_CHUNK

    def header_words(self, n_in: int) -> int:
        return C.ent_header_words(n_in)

    def header_content_bits(self, n_in: int) -> int:
        return 32 * C.ent_header_content_words(C.lc_chunk_count(n_in))

    def encode_words(self, words, n_in: int):
        return C.encode_words_ent(words)

    def decode_words(self, header, payload, n_in: int):
        return C.decode_words_ent(header, payload, n_in)

    def spec(self) -> str:
        return "ent"


@dataclasses.dataclass(frozen=True)
class ShuffleStage:
    """Zigzag sign-fold + byte-plane shuffle (codec.shuffle_words): makes
    the §6 width codes fire on mixed-sign bin streams.  Headerless and
    length-static; `width` must be the lane width of the incoming words
    (the pack width when placed right after `pack`)."""
    width: int = 16
    transmits_len = False

    def capacity_words(self, n_in: int) -> int:
        return C.shuffle_word_count(n_in)

    def header_words(self, n_in: int) -> int:
        return 0

    def header_content_bits(self, n_in: int) -> int:
        return 0

    def encode_words(self, words, n_in: int):
        out = C.shuffle_words(words, self.width)
        return (jnp.zeros((0,), jnp.uint32), out,
                jnp.int32(self.capacity_words(n_in)))

    def decode_words(self, header, payload, n_in: int):
        return C.unshuffle_words(payload, n_in, self.width)

    def spec(self) -> str:
        return f"shuffle:{self.width}"


# ------------------------------------------------------- stage registry ---

def _parse_params(tokens):
    """Split stage arg tokens into (positional list, {key: value})."""
    pos, kw = [], {}
    for t in tokens:
        if "=" in t:
            k, v = t.split("=", 1)
            kw[k] = v
        else:
            pos.append(t)
    return pos, kw


def _parse_chunk(name, tokens):
    if tokens:
        raise ValueError(f"stage {name!r} takes no parameters")
    return ChunkStage(name)


def _parse_shuffle(name, tokens, *, pack_bits):
    pos, kw = _parse_params(tokens)
    if kw or len(pos) > 1:
        raise ValueError("shuffle takes at most one positional width")
    width = int(pos[0]) if pos else pack_bits
    if width not in (8, 16, 32):
        raise ValueError(f"shuffle width must be 8, 16 or 32, got {width}")
    return ShuffleStage(width)


def _parse_ent(name, tokens):
    if tokens:
        raise ValueError(f"stage {name!r} takes no parameters")
    return EntStage()


# name -> parser(name, arg_tokens, pack_bits=...) -> WordStage instance.
# Adding a stage = one class + one entry here (+ a DESIGN.md §7 row).
STAGES = {
    "zero": lambda name, tokens, pack_bits: _parse_chunk(name, tokens),
    "narrow": lambda name, tokens, pack_bits: _parse_chunk(name, tokens),
    "shuffle": lambda name, tokens, pack_bits: _parse_shuffle(
        name, tokens, pack_bits=pack_bits),
    "ent": lambda name, tokens, pack_bits: _parse_ent(name, tokens),
}


def register_stage(name: str, parser) -> None:
    """Register a word stage: parser(name, arg_tokens, pack_bits) -> stage."""
    STAGES[name] = parser


def _unknown_stage_error(tok: str) -> ValueError:
    """Unknown spec token: name every registered stage in BOTH domains
    plus the grammar, so a misplaced stage (a pred token after the
    quantizer, a word token ahead of it) diagnoses itself."""
    return ValueError(
        f"unknown stage {tok!r}; registered value-domain (pred) stages: "
        f"{sorted(P.PRED_STAGES)}; quantizers: {sorted(_QUANT_MODES)}; "
        f"registered word-domain stages: {sorted(STAGES)}; "
        f"grammar: {GRAMMAR}")


def parse_word_stages(stages, pack_bits: int) -> tuple:
    """Resolve a word-stage chain: a tuple of stage objects passes
    through; a spec fragment ("narrow", "shuffle|narrow", "", "none")
    parses via the STAGES registry — the single parser both full
    pipeline specs and per-plane callers (compression/kv.py) share."""
    if isinstance(stages, tuple):
        return stages
    out = []
    for part in str(stages).split("|"):
        part = part.strip()
        if not part or part == "none":
            continue
        tok = part.split(":")
        if tok[0] not in STAGES:
            raise _unknown_stage_error(tok[0])
        out.append(STAGES[tok[0]](tok[0], tok[1:], pack_bits))
    return tuple(out)


# ------------------------------------------------- word-stage chain ops ---

def word_stage_sizes(stages, n_words: int) -> list:
    """[words into stage 0, into stage 1, ..., final capacity] (static)."""
    sizes = [n_words]
    for st in stages:
        sizes.append(st.capacity_words(sizes[-1]))
    return sizes


# The word-stage chains are jitted whole (stages and sizes static): run
# op by op, an archival-size plane materializes every intermediate — the
# `ent` stage expands 2^27 words to 2^29 int32 byte symbols per array.
@functools.partial(jax.jit, static_argnums=(0, 2))
def encode_word_stages(stages, words, n_words: int):
    """Run a word-stage chain over a packed plane (reusable on any word
    stream — gradient shards, KV pages).  Returns (headers tuple,
    payload, transmitted_len)."""
    headers, cur, cur_n = [], words, n_words
    plen = jnp.int32(n_words)
    with obs.scope("codec.word_stages"):
        for st in stages:
            with obs.scope(st.spec()):
                hdr, cur, plen = st.encode_words(cur, cur_n)
            headers.append(hdr)
            cur_n = st.capacity_words(cur_n)
    return tuple(headers), cur, plen


@functools.partial(jax.jit, static_argnums=(0, 3))
def decode_word_stages(stages, headers, payload, n_words: int):
    """Exact inverse of encode_word_stages."""
    sizes = word_stage_sizes(stages, n_words)
    cur = payload
    with obs.scope("codec.word_stages"):
        for st, hdr, n_in in reversed(list(zip(stages, headers,
                                                sizes[:-1]))):
            with obs.scope(st.spec()):
                cur = st.decode_words(hdr, cur, n_in)
    return cur


# -------------------------------------------------------------- pipeline --

@dataclasses.dataclass(frozen=True)
class Pipeline:
    """One LC chain: pred stages -> quantizer -> pack -> word stages.
    Hashable (usable as a jit static argument); `parse_pipeline` /
    `spec()` roundtrip.  `pred` holds value-domain predictor stages
    (core.predict, DESIGN.md §9): exact bijections on the quantized bin
    plane, applied after the quantizer on encode and inverted before
    dequantize on decode, so the §1 guarantee is inherited unchanged."""
    quant: QuantStage
    pack: PackStage
    stages: tuple = ()
    pred: tuple = ()

    def spec(self) -> str:
        return "|".join([p.spec() for p in self.pred]
                        + [self.quant.spec(), self.pack.spec()]
                        + [s.spec() for s in self.stages])

    def qcfg(self) -> QuantizerConfig:
        return QuantizerConfig(mode=self.quant.mode,
                               error_bound=self.quant.eb,
                               bin_bits=self.pack.bits,
                               dtype=self.quant.dtype,
                               outlier_cap_frac=self.quant.cap)

    # --- stage-size bookkeeping (all static ints) -------------------------

    def n_words(self, n: int) -> int:
        """Packed word count entering the first word stage."""
        return C.packed_word_count(n, self.pack.bits)

    def _word_sizes(self, n_words: int) -> list:
        return word_stage_sizes(self.stages, n_words)

    def stage_sizes(self, n: int) -> list:
        """[words into stage 0, into stage 1, ..., final capacity]."""
        return self._word_sizes(self.n_words(n))

    # --- kernel dispatch --------------------------------------------------

    def kernel_dispatch(self) -> str | None:
        """Dotted name of the fused Pallas entry this chain maps onto, or
        None when encode falls back to the jit reference.  Pred chains
        always take the reference path (encode AND decode) — the fused
        quantize+pack kernels have no bin-transform slot yet; this is the
        open row in the DESIGN.md §7 dispatch table."""
        if self.pred:
            return None
        if not self.stages:
            return "repro.kernels.pack.encode_packed"
        if len(self.stages) == 1 and isinstance(self.stages[0], ChunkStage):
            return "repro.kernels.lossless.encode_packed_lc"
        return None

    @staticmethod
    def _auto_kernels() -> bool:
        return jax.default_backend() == "tpu"

    # --- encode -----------------------------------------------------------

    def encode_words(self, words, n_words: int):
        """Run the word stages only (reusable on any packed plane — KV
        pages, gradient shards).  Returns (headers tuple, payload, len)."""
        return encode_word_stages(self.stages, words, n_words)

    def decode_words(self, headers, payload, n_words: int):
        """Exact inverse of encode_words for the word-stage chain."""
        return decode_word_stages(self.stages, headers, payload, n_words)

    def _wrap_packed(self, ep: C.EncodedPacked, n: int) -> Encoded:
        headers, payload, plen = self.encode_words(ep.words, self.n_words(n))
        return Encoded(payload, plen, headers, ep.out_idx, ep.out_payload,
                       ep.n_outliers, ep.overflow, ep.sign_words, ep.eb)

    # --- pred (value-domain) stage plumbing — DESIGN.md §9 ----------------

    def _pred_shape(self, pred_shape, n: int) -> tuple:
        shape = (n,) if pred_shape is None else tuple(pred_shape)
        if int(np.prod(shape)) != n:
            raise ValueError(f"pred_shape {shape} has {int(np.prod(shape))} "
                             f"elements, tensor has {n}")
        return shape

    def _bin_transform(self, pred_shape, n: int):
        """bins -> codes closure for codec.encode_packed, or None."""
        if not self.pred:
            return None
        shape, bits = self._pred_shape(pred_shape, n), self.pack.bits
        return lambda bins: P.encode_pred_stages(self.pred, bins, shape, bits)

    def _bin_untransform(self, pred_shape, n: int):
        """codes -> bins closure for codec.decode_packed, or None."""
        if not self.pred:
            return None
        shape, bits = self._pred_shape(pred_shape, n), self.pack.bits
        return lambda codes: P.decode_pred_stages(self.pred, codes, shape,
                                                  bits)

    def encode(self, x, eb=None, *, kernels: bool | None = None,
               interpret: bool | None = None, return_quantized: bool = False,
               pred_shape=None, verify: bool = False,
               integrity: bool = False):
        """Encode x through the full chain.  kernels=None dispatches the
        fused Pallas path on TPU and the jit reference elsewhere (bit-
        identical); return_quantized forces the reference quantizer so the
        local outlier/recon planes exist for residual bookkeeping.
        `pred_shape` is the value-domain shape the pred stages see
        (defaults to x.shape) — it lets a flattened stream keep its plane
        structure for `lorenzo`/`kvdelta`.

        §12 audit plane: `verify=True` fuses the decode-and-check audit
        into this pass (it shares the reference quantizer's recon plane,
        so it forces the reference path like return_quantized) and
        appends an `audit.AuditReport` to the return; `integrity=True`
        attaches the 32-bit wire checksum as aux (any dispatch path —
        the covered planes are bit-identical across backends).  Returns
        enc | (enc, qt) | (enc, report) | (enc, qt, report)."""
        n = int(np.prod(x.shape))
        with obs.span("codec.encode", x, chain=self.spec(), n=n):
            if pred_shape is None:
                pred_shape = tuple(x.shape)
            use_k = (self._auto_kernels() if kernels is None else kernels)
            if use_k and not return_quantized and not verify:
                target = self.kernel_dispatch()
                if target == "repro.kernels.pack.encode_packed":
                    from repro.kernels import pack as _kp      # lazy: circular
                    ep = _kp.encode_packed(x, self.qcfg(), eb,
                                           interpret=interpret)
                    enc = self._wrap_packed(ep, n)
                    return A.attach_checksum(enc) if integrity else enc
                if target == "repro.kernels.lossless.encode_packed_lc":
                    from repro.kernels import lossless as _kl
                    lc = _kl.encode_packed_lc(x, self.qcfg(), eb,
                                              stage=self.stages[0].mode,
                                              interpret=interpret)
                    enc = Encoded(lc.payload, lc.payload_len,
                                  (lc.header_words,), lc.out_idx,
                                  lc.out_payload, lc.n_outliers, lc.overflow,
                                  lc.sign_words, lc.eb)
                    return A.attach_checksum(enc) if integrity else enc
            ep, qt = C.encode_packed(x, self.qcfg(), eb, return_quantized=True,
                                     bin_transform=self._bin_transform(
                                         pred_shape, n))
            enc = self._wrap_packed(ep, n)
            if integrity:
                enc = A.attach_checksum(enc)
            if verify:
                report = A.audit_report(
                    x, qt, self.qcfg(),
                    eb=enc.eb if enc.eb is not None else eb,
                    overflow=enc.overflow, n_outliers=enc.n_outliers)
                return (enc, qt, report) if return_quantized else (enc, report)
            return (enc, qt) if return_quantized else enc

    # --- decode -----------------------------------------------------------

    def decode(self, enc: Encoded, n: int | None = None, shape=None,
               dtype=None, *, kernels: bool | None = None,
               interpret: bool | None = None, pred_shape=None,
               verify: bool = False):
        """Invert the chain: word stages in reverse, pred stages inverted
        on the bin plane, then unpack + dequantize + exact outlier
        restore.  Bit-identical between the fused-kernel and reference
        back ends.  `pred_shape` must match the encode-side value (it
        defaults to `shape`, falling back to the flat stream).

        §12 guards: a transmitted `payload_len` outside the padded
        plane's [0, capacity] raises `audit.WireIntegrityError` host-side
        (traced lengths are clamped inside the codec's gathers instead);
        `verify=True` re-checks the carried integrity checksum before
        decoding (host-side — raises on mismatch; requires a wire
        encoded with integrity=True)."""
        if n is None:
            if shape is None:
                raise ValueError("decode needs n or shape")
            n = int(np.prod(shape))
        if pred_shape is None and shape is not None:
            pred_shape = tuple(shape)
        with obs.span("codec.decode", enc.payload, chain=self.spec(), n=n):
            with obs.span("codec.decode.check_len", enc.payload_len):
                A.check_payload_len(enc.payload_len, enc.payload.shape[0],
                                    what=f"Encoded[{self.spec()}]")
            if verify:
                ok = A.verify_wire(enc)
                if not isinstance(ok, jax.core.Tracer) and not bool(ok):
                    raise A.WireIntegrityError(
                        f"Encoded[{self.spec()}]: checksum mismatch on decode")
            words = self.decode_words(enc.headers, enc.payload, self.n_words(n))
            ep = C.EncodedPacked(words, enc.out_idx, enc.out_payload,
                                 enc.n_outliers, enc.overflow, enc.sign_words,
                                 enc.eb)
            use_k = (self._auto_kernels() if kernels is None else kernels)
            if use_k and not self.pred:
                from repro.kernels import pack as _kp          # lazy: circular
                return _kp.decode_packed(ep, self.qcfg(), n=n, shape=shape,
                                         dtype=dtype, interpret=interpret)
            return C.decode_packed(ep, self.qcfg(), n=n, shape=shape,
                                   dtype=dtype,
                                   bin_untransform=self._bin_untransform(
                                       pred_shape, n))

    def roundtrip(self, x, eb=None, **kw):
        return self.decode(self.encode(x, eb, **kw), shape=x.shape, **kw)

    # --- honest wire accounting -------------------------------------------

    def _base_bits(self, enc: Encoded) -> int:
        bits = 64 + enc.out_idx.shape[0] * (32 + 32)
        if enc.sign_words is not None:
            bits += 32 * enc.sign_words.shape[0]
        if enc.checksum is not None:
            bits += 32                             # §12 integrity digest
        # pred stages transmit their header CONTENT here (§9).  Every
        # shipped predictor is a static bijection with zero header bits,
        # but the accounting slot is part of the value-stage contract, so
        # a future parameterized predictor stays bit-exact for free.
        return bits + sum(st.header_content_bits() for st in self.pred)

    def wire_bits(self, enc: Encoded, n: int | None = None):
        """Transmitted wire size in bits: the final payload's transmitted
        prefix, every stage's header CONTENT (tile padding excluded — the
        receiver re-pads), the outlier table, sign plane, and the 64-bit
        packed header (+32 for a transmitted length).  A static int for
        static chains; traced f32 otherwise (exact through 2^24 words —
        see EncodedLC.wire_bits for the rationale).

        Pass `n` (element count) for exact per-stage input sizes; without
        it the final payload capacity is used, which is exact for every
        registered stage (header content depends only on the stage's
        chunk count, recoverable from any tile-aligned capacity — part of
        the stage contract).

        The traced branch routes through `codec.transmitted_bits` —
        exact int32 word accumulation with one f32 conversion (see its
        docstring for the precision envelope); adding f32 bit totals
        instead rounded past 2^24 words."""
        if not self.stages:
            return self._base_bits(enc) + 32 * enc.payload.shape[0]
        if n is not None:
            sizes = self.stage_sizes(n)[:-1]
        else:
            sizes = [enc.payload.shape[0]] * len(self.stages)
        hdr = sum(st.header_content_bits(sz)
                  for st, sz in zip(self.stages, sizes))
        if self.stages[-1].transmits_len:
            return C.transmitted_bits(enc.payload_len,
                                      self._base_bits(enc) + hdr + 32)
        return self._base_bits(enc) + hdr + 32 * enc.payload.shape[0]

    def wire_bytes(self, enc: Encoded, n: int | None = None):
        b = self.wire_bits(enc, n)
        return b // 8 if isinstance(b, int) else b / 8.0

    def capacity_bytes(self, enc: Encoded) -> int:
        """Static upper bound: what a padded all-gather buffer holds."""
        b = (enc.payload.size + enc.out_idx.size + enc.out_payload.size
             + sum(h.size for h in enc.headers)) * 4 + 8
        if enc.sign_words is not None:
            b += enc.sign_words.size * 4
        if enc.checksum is not None:
            b += 4                                 # §12 integrity digest
        if self.stages:
            b += 4                                 # transmitted length field
        return b

    # --- per-stage reporting ----------------------------------------------

    def stage_report(self, x, eb=None, pred_shape=None):
        """[(label, transmitted_bits_after_stage), ...] through the chain,
        starting from the raw tensor.  Reference path (host-callable).
        Pred stages are bijections on the packed plane (zero header bits,
        §9), so they fold into the base row's label — the word-stage rows
        then show what the residual plane actually bought."""
        n = int(np.prod(x.shape))
        if pred_shape is None:
            pred_shape = tuple(x.shape)
        ep, _ = C.encode_packed(x, self.qcfg(), eb, return_quantized=True,
                                bin_transform=self._bin_transform(
                                    pred_shape, n))
        base = self._base_bits(
            Encoded(ep.words, jnp.int32(0), (), ep.out_idx, ep.out_payload,
                    ep.n_outliers, ep.overflow, ep.sign_words, ep.eb))
        base_label = "|".join([p.spec() for p in self.pred]
                              + [self.quant.spec(), self.pack.spec()])
        rows = [("raw", n * np.dtype(self.quant.dtype).itemsize * 8),
                (base_label, base + 32 * ep.words.shape[0])]
        cur, cur_n = ep.words, self.n_words(n)
        hdr_bits = 0
        for st in self.stages:
            _, cur, plen = st.encode_words(cur, cur_n)
            hdr_bits += st.header_content_bits(cur_n)
            cur_n = st.capacity_words(cur_n)
            # mirror wire_bits exactly: +32 (the transmitted length
            # field) only when this prefix's final stage is
            # length-variable, through the same shared accounting
            if st.transmits_len:
                bits = C.transmitted_bits(plen, base + hdr_bits + 32)
            else:
                bits = base + hdr_bits + 32 * cur.shape[0]
            rows.append((st.spec(), float(bits)))
        return rows


# ------------------------------------------------------------ the parser --

def parse_pipeline(spec) -> Pipeline:
    """Parse a pipeline spec string ("delta|abs:1e-3|pack:16|zero|narrow")
    into a Pipeline.  Grammar (GRAMMAR): stages are '|'-separated; each
    stage is name[:arg][:key=value...].  Leading tokens naming registered
    pred stages (predict.PRED_STAGES) form the value-domain chain; the
    next stage must be a quantizer (abs|rel|noa, positional eb, optional
    cap=/dtype=), then pack:<bits>, then registered word stages (STAGES).
    `Pipeline.spec()` is the exact inverse."""
    if isinstance(spec, Pipeline):
        return spec
    parts = [p.strip() for p in str(spec).split("|") if p.strip()]
    pred = []
    while parts and parts[0].split(":")[0] in P.PRED_STAGES:
        tok = parts.pop(0).split(":")
        pred.append(P.PRED_STAGES[tok[0]](tok[0], tok[1:]))
    if len(parts) < 2:
        raise ValueError(
            f"pipeline spec needs at least 'quant:<eb>|pack:<bits>', "
            f"got {spec!r}; grammar: {GRAMMAR}")
    qtok = parts[0].split(":")
    if qtok[0] not in _QUANT_MODES:
        raise _unknown_stage_error(qtok[0])
    pos, kw = _parse_params(qtok[1:])
    if len(pos) != 1:
        raise ValueError(f"quantizer stage needs exactly one error bound, "
                         f"got {parts[0]!r}")
    bad = set(kw) - {"cap", "dtype"}
    if bad:
        raise ValueError(f"unknown quantizer parameters {sorted(bad)}")
    quant = QuantStage(qtok[0], float(pos[0]),
                       float(kw.get("cap", _CAP_DEFAULT)),
                       kw.get("dtype", "float32"))
    ptok = parts[1].split(":")
    if ptok[0] != "pack" or len(ptok) != 2:
        raise ValueError(f"second stage must be 'pack:<bits>', "
                         f"got {parts[1]!r}")
    pack = PackStage(int(ptok[1]))
    if pack.bits not in (8, 16, 32):
        raise ValueError(f"pack bits must be 8, 16 or 32, got {pack.bits}")
    stages = parse_word_stages("|".join(parts[2:]), pack.bits)
    pipe = Pipeline(quant, pack, stages, tuple(pred))
    pipe.qcfg()                       # validate the combination eagerly
    return pipe
