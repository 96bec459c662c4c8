"""The Transport API (DESIGN.md §8) — ONE choke point for every
compressed wire that crosses a mesh axis.

The paper's guarantee is an end-to-end property: encode -> transmit ->
decode must return every value within eb of its original or bit-for-bit
identical.  Before this module the "transmit" leg was scattered — the
gradient wire hand-rolled five `lax.all_gather` calls over `Encoded`
fields, the KV migration wire did its own pytree-map gather, and serving
moved raw f32 pages.  `Transport` centralizes all of it:

    all_gather(wire, axis)           pytree-aware gather of any wire form
                                     (Encoded, CompressedShard, PackedKV)
    reduce_sum / reduce_mean(...)    the compressed-gradient collective:
                                     a packed-domain ring (lax.ppermute)
                                     when the shards are grid-compatible,
                                     else gather+dequantize+reduce —
                                     bit-identical either way (§8)
    send_pages(wire, src, dst, axis) point-to-point wire movement
                                     (prefill→decode KV disaggregation)
    bytes_moved(wire, op=...)        transmitted-byte accounting for a
                                     whole collective, derived from
                                     `wire_bytes` below

`wire_bytes(wire)` is the single transmitted-bytes accessor all three
former accountings (`CompressedShard.nbytes`, `PackedKV.wire_nbytes`,
the pre-pipeline `lc_wire_bytes`) now route through, so reported and
shipped bytes cannot drift between layers.

PACKED-DOMAIN REDUCE (the §8 compatibility rule).  `reduce_sum` may
reduce in the packed domain — a ring over `lax.ppermute` whose hop
payload is the §4 uint32 word plane, with bins accumulated as integers
and dequantized ONCE at the end — exactly when the result is provably
bit-identical to the gather+dequantize+reduce reference:

  * static:  the chain is ABS with no word stages (linear dequant, no
             data-dependent payload), the axis size p is statically
             known, and p * maxbin < 2^24 (every partial sum of bins is
             an exact f32 multiple of the pow2 step eb2);
  * runtime (pmax/pmin-agreed so all pods branch together): every pod
             quantized on the SAME grid (bit-equal per-tensor eb) and
             no pod has outliers (the exact-payload scatter is empty).

Under those conditions sum_i(bins_i) * eb2 and sum_i(bins_i * eb2) are
the same exactly-representable real number in any summation order, so
the branch cannot change a single bit — pinned by tests/test_transport.
Everything else (REL/NOA, staged chains, mixed grids, outliers) takes
the gather path, which IS the pre-transport reference code path.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from .. import obs
from . import audit as A
from . import codec as C
from .pipeline import Encoded, Pipeline
from .quantizer import dequantize_abs
from .select import SelectedWire


def axis_size_static(axis) -> int | None:
    """Static size of a named mesh axis (needed to build a ring perm), or
    None outside a context that binds it — callers fall back to gather."""
    try:
        return int(jax.lax.axis_size(axis))
    except NameError:                                  # unbound axis name
        return None


# ------------------------------------------------------ byte accounting ---

def _kv_wire_bytes(wire):
    """Per-page accounting for a PackedKV-shaped wire (payload /
    payload_len / stages / eb2 / outlier table / overflow).  Traced when a
    stage is length-variable; +4/page for the transmitted length itself.
    Per page each stage costs its header CONTENT bits only — not the
    tile-padded stored plane (zeros the receiver re-pads).

    BITS accumulate across stages and pages and divide ONCE at the end —
    flooring each stage's content to bytes per page dropped sub-byte
    headers and drifted from `Pipeline.wire_bits` (which sums bits).
    The traced payload word count sums as exact int32 through the
    shared `codec.transmitted_bits` accounting (see its docstring for
    the precision envelope), where the old per-page f32 sum silently
    rounded past 2^24 total words."""
    cap = wire.payload.shape[-1]
    n_pages = wire.payload_len.size
    checksum_bits = 32 if getattr(wire, "checksum", None) is not None else 0
    sel = getattr(wire, "select", None)
    if sel is not None:
        # §11 per-page selection: each page transmits a 1-byte chain id
        # and its own length, and pays the CHOSEN fragment's header
        # content — dispatched per page on the transmitted ids
        hcb = jnp.asarray([sel.header_content_bits(i, cap)
                           for i in range(len(sel.chains))], jnp.int32)
        chain_ids = wire.chain_id.reshape(-1).astype(jnp.int32)
        hdr_bits = jnp.sum(jnp.take(hcb, chain_ids)).astype(jnp.float32)
        static_bits = n_pages * (8 + 32) + checksum_bits
        static_bits += (wire.eb2.size * 32 + wire.out_idx.size * 32
                        + wire.out_val.size * 32 + wire.overflow.size * 8)
        words = jnp.sum(wire.payload_len.astype(jnp.int32))
        return (C.transmitted_bits(words, static_bits) + hdr_bits) / 8.0
    static_bits = checksum_bits + n_pages * sum(st.header_content_bits(cap)
                                                for st in wire.stages)
    # per-page pred stages (§9) transmit their header content too — zero
    # for the shipped static bijections, but the slot keeps this accessor
    # bit-exact against Pipeline.wire_bits for any future predictor
    static_bits += n_pages * sum(st.header_content_bits()
                                 for st in getattr(wire, "pred", ()))
    static_bits += (wire.eb2.size * 32 + wire.out_idx.size * 32
                    + wire.out_val.size * 32 + wire.overflow.size * 8)
    if wire.stages and wire.stages[-1].transmits_len:
        static_bits += n_pages * 32            # the transmitted lengths
        words = jnp.sum(wire.payload_len.astype(jnp.int32))
        return C.transmitted_bits(words, static_bits) / 8.0
    bits = static_bits + 32 * wire.payload.size
    return bits // 8 if bits % 8 == 0 else bits / 8.0


def wire_bytes(wire, *, pipe: Pipeline | None = None, n: int | None = None):
    """Transmitted bytes of ONE wire object — the single accounting
    accessor (DESIGN.md §8).  Dispatches on the wire form:

      * `Encoded` + its `pipe` (and element count `n`): the pipeline's
        transmitted-prefix accounting (`Pipeline.wire_bytes`);
      * a shard carrying its own pipe/n (`CompressedShard`): same, using
        the carried statics;
      * a PackedKV-shaped per-page wire: the per-page chunk accounting;
      * a NamedTuple of wires (e.g. `models.serve.PackedCache`): the sum
        of its fields;
      * a list/tuple of wires (a streamed page sequence — the engine's
        per-page migration ledger, DESIGN.md §10): the sum of its items;
      * a raw array: moves at full width (`size * itemsize`).

    Static int for static chains, traced scalar when a length-variable
    stage makes the payload data-dependent."""
    if isinstance(wire, Encoded):
        if pipe is None:
            raise TypeError("wire_bytes(Encoded) needs pipe= (and n=)")
        return pipe.wire_bytes(wire, n)
    if isinstance(wire, SelectedWire):
        # §11 selector wire: the selected chain's own accounting plus
        # the transmitted chain-id byte, dispatched on the chain id
        if pipe is None or n is None:
            raise TypeError("wire_bytes(SelectedWire) needs pipe= and n=")
        return pipe.wire_bytes(wire, n)
    if isinstance(getattr(wire, "enc", None), (Encoded, SelectedWire)):
        return wire.pipe.wire_bytes(wire.enc, wire.n if n is None else n)
    if hasattr(wire, "eb2") and hasattr(wire, "payload"):
        return _kv_wire_bytes(wire)
    if hasattr(wire, "_fields") or isinstance(wire, (list, tuple)):
        total = 0
        for field in wire:
            total = total + wire_bytes(field)
        return total
    if hasattr(wire, "dtype") and hasattr(wire, "size"):
        return wire.size * wire.dtype.itemsize
    raise TypeError(f"wire_bytes cannot account a {type(wire).__name__}")


# ------------------------------------------------------------ transport ---

@dataclasses.dataclass(frozen=True)
class Transport:
    """Moves compressed wires across mesh axes.  Stateless and hashable;
    `TRANSPORT` below is the default instance consumers share.

    reduce: 'auto' takes the packed-domain ring whenever the §8
    compatibility rule allows (runtime-agreed, bit-identical); 'gather'
    pins the gather+dequantize+reduce reference path unconditionally.

    fault: TEST-ONLY in-graph corruption hook (DESIGN.md §12): applied
    to every received wire pytree right after the collective, BEFORE
    any verify — the fault-injection harness (`runtime.guard`) uses it
    to prove the receive-side checks catch in-flight corruption.  Must
    be a hashable callable (wire) -> wire; None in production.
    """
    reduce: str = "auto"               # 'auto' | 'gather'
    fault: Callable | None = None      # §12 test-only corruption hook

    def __post_init__(self):
        if self.reduce not in ("auto", "gather"):
            raise ValueError(f"reduce must be 'auto' or 'gather', "
                             f"got {self.reduce!r}")

    # --- collectives ------------------------------------------------------

    def _verify_received(self, wire, verify, what: str):
        """Shared §12 receive-side check: verify=None passes the wire
        through untouched (and unchecked); 'mask' appends per-shard
        verdicts from the carried checksums — (wire, bool[axis_size]);
        'raise' checks host-side and raises `WireIntegrityError` (eager
        only — inside jit/shard_map use 'mask' and route the verdicts to
        a degradation policy in-graph)."""
        if verify is None:
            return wire
        ok = A.verify_gathered(wire)
        if verify == "mask":
            return wire, ok
        if verify == "raise":
            if isinstance(ok, jax.core.Tracer):
                raise ValueError(
                    f"{what}: verify='raise' needs eager execution; use "
                    f"verify='mask' inside jit/shard_map (DESIGN.md §12)")
            if not bool(jnp.all(ok)):
                raise A.WireIntegrityError(
                    f"{what}: received wire failed its integrity "
                    f"checksum (shard mask {ok.tolist()})")
            return wire
        raise ValueError(f"verify must be None, 'mask' or 'raise', "
                         f"got {verify!r}")

    def all_gather(self, wire, axis, *, verify=None):
        """All-gather any wire pytree over a mesh axis (call inside
        shard_map); every array leaf grows a leading axis of the axis
        size.  Static metadata (pipelines, stage chains) rides in the
        pytree aux data untouched.  `verify` (§12) checks each received
        shard's carried checksum: 'mask' returns (gathered, bool[p]),
        'raise' raises eagerly on any mismatch — requires wires encoded
        with integrity=True."""
        gathered = jax.tree.map(lambda a: jax.lax.all_gather(a, axis), wire)
        if self.fault is not None:
            gathered = self.fault(gathered)
        return self._verify_received(gathered, verify, "all_gather")

    def _ring_ok(self, pipe: Pipeline, qc, p) -> bool:
        # Pred chains never ring-reduce: the wire carries folded residual
        # codes, and the delta of a sum is not the sum of the deltas once
        # each shard folds independently — decode-then-sum is the only
        # exact path (DESIGN.md §9), so they take the gather branch.
        # Selector wires (§11) likewise: each shard picked its own chain,
        # so the word planes are not grid-aligned across pods.
        return (self.reduce == "auto" and isinstance(pipe, Pipeline)
                and qc.mode == "abs"
                and not pipe.stages and not pipe.pred
                and p is not None and p > 1
                and p * qc.maxbin < (1 << 24))

    def _ring_compat(self, enc, axis):
        # runtime agreement: same pow2 grid everywhere + no outliers
        # anywhere (NaN eb compares unequal -> gather, like any mismatch)
        compat = jax.lax.pmax(enc.n_outliers, axis) == 0
        if enc.eb is not None:
            eb_hi = jax.lax.pmax(enc.eb, axis)
            eb_lo = -jax.lax.pmax(-enc.eb, axis)
            compat = compat & (eb_hi == eb_lo)
        return compat

    def _check_integrity_arg(self, enc, integrity: str):
        """Host-side validation for the checked reduce (§12): the policy
        must exist, be expressible in-graph ('drop' is the only one — a
        traced collective cannot raise or re-request), and the wire must
        carry a checksum for the gather fallback's per-shard verdicts."""
        A.get_policy(integrity)            # fail fast on unknown names
        if integrity != "drop":
            raise ValueError(
                f"reduce integrity={integrity!r}: in-graph reduction "
                f"supports only the 'drop' policy (mask + renormalize); "
                f"route 'raise'/'rerequest' host-side via "
                f"all_gather(verify='mask') (DESIGN.md §12)")
        if not A.has_checksum(enc):
            raise ValueError(
                "reduce with integrity= needs encode(integrity=True) "
                "wires — no checksum carried (DESIGN.md §12)")

    def reduce_sum(self, enc: Encoded, pipe: Pipeline, n: int, axis, *,
                   integrity: str | None = None):
        """Sum of every pod's decoded tensor over `axis` (call inside
        shard_map).  Ring-reduces in the packed domain when the §8
        compatibility rule holds (checked statically + runtime-agreed via
        pmax/pmin so all pods branch together); otherwise — and always
        with reduce='gather' — gathers the wires and sums the per-pod
        decodes, the pre-transport reference path.  Bit-identical either
        way.

        `integrity='drop'` (§12) verifies every received contribution —
        per-hop `plane_checksum`s on the ring (each hop payload rides
        with its owner's digest, so corruption at ANY hop is caught by
        every downstream rank), per-shard wire checksums on the gather
        path — and drops failed contributions from the sum.  Requires
        encode(integrity=True) wires.  NOTE: the dropped-shard sum is a
        partial sum; use `reduce_mean` for the renormalized mean."""
        if integrity is None:
            qc = pipe.qcfg()
            p = axis_size_static(axis)
            if not self._ring_ok(pipe, qc, p):
                return self._gather_sum(enc, pipe, n, axis)
            return jax.lax.cond(
                self._ring_compat(enc, axis),
                lambda _: self._ring_sum(enc, qc, n, axis, p),
                lambda _: self._gather_sum(enc, pipe, n, axis),
                None)
        total, _ = self._reduce_checked(enc, pipe, n, axis, integrity)
        return total

    def reduce_mean(self, enc: Encoded, pipe: Pipeline, n: int, axis, *,
                    integrity: str | None = None, return_valid: bool = False):
        """reduce_sum / axis_size — the compressed-mean collective.

        `integrity='drop'` (§12): failed contributions (hop-corrupt ring
        payloads, checksum-failed gathered shards) are dropped and the
        mean renormalizes over the contributions THIS rank verified —
        the `compressed_mean` drop semantics applied to the collective.
        Each rank divides by its own valid count, so ranks downstream of
        a corrupt link degrade independently instead of silently
        averaging garbage.  `return_valid=True` appends the per-rank
        valid-contribution count (int32; == axis size on a clean run) —
        the observable `benchmarks/audit_bench.py`'s ring detection row
        pins."""
        if integrity is None:
            p = jax.lax.axis_size(axis)
            mean = self.reduce_sum(enc, pipe, n, axis) / p
            return (mean, jax.lax.psum(jnp.int32(1), axis)) \
                if return_valid else mean
        total, n_valid = self._reduce_checked(enc, pipe, n, axis, integrity)
        mean = total / jnp.maximum(n_valid, 1).astype(total.dtype)
        return (mean, n_valid) if return_valid else mean

    def send_pages(self, wire, src: int, dst: int, axis, *, verify=None):
        """Point-to-point: move a wire pytree from mesh rank `src` to
        `dst` along `axis` (call inside shard_map).  Rank `dst` receives
        `src`'s arrays bit-for-bit; every other rank receives zeros
        (ppermute semantics) — callers select the destination shard.
        This is the prefill→decode KV migration primitive: only the wire
        arrays cross the link, never a dequantized plane.

        `verify='mask'` (§12) appends the received wire's checksum
        verdict (a 0-d bool per shard — only rank `dst`'s verdict is
        meaningful; the other ranks verify ppermute's zero fill)."""
        perm = [(src, dst)]
        moved = jax.tree.map(
            lambda a: jax.lax.ppermute(a, axis, perm), wire)
        if self.fault is not None:
            moved = self.fault(moved)
        if verify is None:
            return moved
        ok = A.verify_wire(moved)
        if verify == "mask":
            return moved, ok
        if verify == "raise":
            if isinstance(ok, jax.core.Tracer):
                raise ValueError(
                    "send_pages: verify='raise' needs eager execution; "
                    "use verify='mask' inside jit/shard_map")
            if not bool(ok):
                raise A.WireIntegrityError(
                    "send_pages: received wire failed its integrity "
                    "checksum")
            return moved
        raise ValueError(f"verify must be None, 'mask' or 'raise', "
                         f"got {verify!r}")

    # --- reduce internals -------------------------------------------------

    def _gather_sum(self, enc, pipe, n, axis):
        # the reference path: gather every pod's wire, run the pipeline's
        # exact inverse per pod, sum.  Ops and order match the
        # pre-transport compressed_mean gather/dequant exactly (pinned by
        # tests/test_transport.py), so the refactor cannot move a bit.
        with obs.scope("transport.gather"):
            enc_all = self.all_gather(enc, axis)
            dec = jax.vmap(lambda e: pipe.decode(e, n=n, kernels=False))(
                enc_all)
            return jnp.sum(dec, axis=0)

    def _ring_sum(self, enc, qc, n, axis, p: int):
        # packed-domain ring: each hop moves the §4 word plane (bin_bits
        # per value) to the next rank; bins accumulate as exact int32 and
        # dequantize ONCE.  Valid only under the §8 compatibility rule —
        # reduce_sum guards it; do not call directly without those checks.
        perm = [(i, (i + 1) % p) for i in range(p)]
        with obs.scope("transport.ring"):
            total = C.unpack_words(enc.payload, n, qc.bin_bits)
            cur = enc.payload
            for _ in range(p - 1):
                cur = jax.lax.ppermute(cur, axis, perm)
                total = total + C.unpack_words(cur, n, qc.bin_bits)
            return dequantize_abs(total, qc, eb=enc.eb, dtype=jnp.float32)

    def _reduce_checked(self, enc, pipe, n, axis, integrity: str):
        # the §12 verified reduce: -> (masked sum, per-rank valid count).
        # Both branches of the cond return the same (f32[n], int32) pair.
        self._check_integrity_arg(enc, integrity)
        qc = pipe.qcfg()
        p = axis_size_static(axis)
        if not self._ring_ok(pipe, qc, p):
            return self._gather_sum_checked(enc, pipe, n, axis)
        return jax.lax.cond(
            self._ring_compat(enc, axis),
            lambda _: self._ring_sum_checked(enc, qc, n, axis, p),
            lambda _: self._gather_sum_checked(enc, pipe, n, axis),
            None)

    def _gather_sum_checked(self, enc, pipe, n, axis):
        # gather fallback of the verified reduce: per-shard whole-wire
        # checksum verdicts mask the per-pod decodes out of the sum.
        with obs.scope("transport.gather"):
            enc_all, ok = self.all_gather(enc, axis, verify="mask")
            dec = jax.vmap(lambda e: pipe.decode(e, n=n, kernels=False))(
                enc_all)
            mask = ok.reshape((-1,) + (1,) * (dec.ndim - 1))
            total = jnp.sum(jnp.where(mask, dec, jnp.zeros((), dec.dtype)),
                            axis=0)
            return total, jnp.sum(ok.astype(jnp.int32))

    def _ring_sum_checked(self, enc, qc, n, axis, p: int):
        # verified ring (§12): the hop wire is (payload, owner digest) —
        # the digest is `audit.plane_checksum` computed ONCE by the
        # plane's owner and ppermuted alongside through every hop, so a
        # flip introduced at ANY link poisons the recomputed fold at
        # every downstream rank (the whole-wire checksum never sees
        # intermediate hops).  Failed hops are masked out of the int32
        # bin accumulation and the valid count; own bins always count.
        perm = [(i, (i + 1) % p) for i in range(p)]
        with obs.scope("transport.ring"):
            total = C.unpack_words(enc.payload, n, qc.bin_bits)
            cur, cs = enc.payload, A.plane_checksum(enc.payload)
            n_valid = jnp.int32(1)
            for _ in range(p - 1):
                cur = jax.lax.ppermute(cur, axis, perm)
                cs = jax.lax.ppermute(cs, axis, perm)
                if self.fault is not None:     # §12 hook: corrupt the hop pair
                    cur, cs = self.fault((cur, cs))
                ok = A.plane_checksum(cur) == cs
                bins = C.unpack_words(cur, n, qc.bin_bits)
                total = total + jnp.where(ok, bins, jnp.zeros((), bins.dtype))
                n_valid = n_valid + ok.astype(jnp.int32)
            return (dequantize_abs(total, qc, eb=enc.eb, dtype=jnp.float32),
                    n_valid)

    # --- accounting -------------------------------------------------------

    def bytes_moved(self, wire, *, op: str = "all_gather",
                    axis_size: int = 1, pipe: Pipeline | None = None,
                    n: int | None = None):
        """Total bytes a collective moves across the axis, from the
        single `wire_bytes` accessor:

          op='send_pages'   one copy of the wire (src -> dst);
          op='all_gather'   every member ships its wire to the other
                            p - 1 members: p * (p - 1) * wire_bytes;
          op='reduce_sum' / 'reduce_mean'
                            the gather-path bound (== all_gather).  When
                            the §8 ring fires it moves only the word
                            plane per hop — strictly less; this reports
                            the path that is always available.
        """
        w = wire_bytes(wire, pipe=pipe, n=n)
        if op == "send_pages":
            return w
        if op in ("all_gather", "reduce_sum", "reduce_mean"):
            if axis_size < 2:
                # p*(p-1)*w would silently report 0 bytes for a
                # degenerate axis — demand the real size instead
                raise ValueError(
                    f"bytes_moved(op={op!r}) needs axis_size >= 2, "
                    f"got {axis_size}")
            return axis_size * (axis_size - 1) * w
        raise ValueError(f"unknown op {op!r}")


TRANSPORT = Transport()
