"""Guaranteed-error-bounded gradient compression for the cross-pod
all-reduce — the paper's quantizer on the slowest wire in the system.

Design (DESIGN.md §2/§4/§5/§7/§8):
  * Within a pod, gradients reduce over the fast 'data'/'model' axes in
    full precision (GSPMD handles those — the links are wide).
  * Across pods, each pod quantizes its pod-local gradient through a
    compression PIPELINE (core.pipeline, DESIGN.md §7) — an ABS quantizer
    with a per-tensor NOA-style bound eb = eb_rel * rms(g), the §4
    bit-pack, and any chain of lossless word stages — into ONE `Encoded`
    wire container.  The TRANSPORT layer (core.transport, DESIGN.md §8)
    moves it: `Transport.reduce_sum` ring-reduces in the packed domain
    when every pod sits on the same pow2 grid with no outliers, and
    otherwise gathers the wires and sums the per-pod decodes —
    bit-identical either way.  Nothing wider than the final payload
    plane crosses the collective — `CompressedShard.nbytes()` is the
    real measured footprint (`benchmarks/run.py gradwire`/`lossless`),
    routed through the one `transport.wire_bytes` accessor.
  * LOSSLESS STAGES (DESIGN.md §6/§7): with word stages in the pipeline
    (e.g. "abs:1|pack:8|narrow", or "abs:1|pack:16|narrow|ent" to
    entropy-code the surviving chunk bytes — a spec silent about cap=
    inherits this config's outlier_cap_frac; an explicit cap= wins),
    the packed words are further coded before the gather — all-zero
    chunks dropped, the rest narrowed/entropy-coded, exactly
    reversible, so the bound is untouched.  XLA's
    static shapes force the gathered payload to be padded to capacity;
    the honest footprint is the transmitted prefix (`payload_len`),
    which is what `nbytes()` measures and what a real transport (or a
    size-psum'd ragged gather) would move.
  * ERROR FEEDBACK: the residual g - shipped is carried to the next step,
    so the long-run update is unbiased.  The paper's guarantee bounds the
    per-step residual ELEMENTWISE: |e_i| <= eb (outliers ship exactly, so
    their residual is 0) — heuristic compressors cannot promise that, and
    it is what keeps the error-feedback buffer from drifting.
  * OVERFLOW: if the outlier cap is exceeded the compact encoding cannot
    honor the bound; a pmax-agreed flag flips that tensor to the lossless
    psum for the step (lax.cond) — the guarantee is never silently
    dropped (the paper's core discipline).

These functions use explicit collectives over the 'pod' axis and are
called INSIDE a shard_map set up by launch/train.py; 'data'/'model'
sharding stays with GSPMD.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import select as SEL
from repro.core.pipeline import (Encoded, Pipeline, PackStage, QuantStage,
                                 parse_pipeline)
from repro.core.transport import TRANSPORT, Transport, wire_bytes as _wire_bytes


class GradCompressionConfig(NamedTuple):
    eb_rel: float = 2.0 ** -8       # bound relative to grad RMS
    bin_bits: int = 8               # used when `pipeline` is empty
    outlier_cap_frac: float = 1 / 64
    enabled: bool = True
    pipeline: str = ""              # spec, e.g. "abs:1.0|pack:8|narrow" or
    #                                 "delta|abs:1.0|pack:16|narrow|ent";
    #                                 the quantizer eb is a placeholder
    #                                 (the traced per-tensor eb overrides)
    #                                 and a spec without cap= inherits
    #                                 outlier_cap_frac.  Pred-bearing
    #                                 specs (DESIGN.md §9) see the shard
    #                                 as one flat stream; their residual
    #                                 wires never ring-reduce, so
    #                                 reduce_sum takes the
    #                                 gather+dequantize branch (§8).
    #                                 'auto' / 'auto:SET' (DESIGN.md §11)
    #                                 resolves to a Selector: the chain
    #                                 is chosen PER SHARD at encode time
    #                                 from the set's candidates; selector
    #                                 wires also always gather.

    def pipe(self):
        """The compression pipeline this config describes (`Pipeline`,
        or a §11 `Selector` for 'auto' specs).  `pipeline` wins;
        otherwise a stage-free chain is built from eb_rel/bin_bits.
        The quantizer must be ABS: the wire's per-tensor bound
        eb_rel * rms(g) is an ABS bound, and the transport's
        gather/dequant moves exactly the ABS planes (no sign plane)."""
        if SEL.is_auto_spec(self.pipeline):
            sel = SEL.parse_selector(self.pipeline)
            if sel.quant.mode != "abs":
                raise ValueError(
                    f"the gradient wire needs an 'abs' quantizer stage; "
                    f"selector set {sel.name!r} has {sel.quant.mode!r}")
            from repro.configs.registry import SELECTOR_SETS
            if "cap=" not in SELECTOR_SETS[sel.name]["base"]:
                # like plain specs: a base silent about the outlier cap
                # inherits this config's; an explicit cap= wins
                sel = dataclasses.replace(sel, chains=tuple(
                    dataclasses.replace(p, quant=dataclasses.replace(
                        p.quant, cap=self.outlier_cap_frac))
                    for p in sel.chains))
            return sel
        if self.pipeline:
            pipe = parse_pipeline(self.pipeline)
            if pipe.quant.mode != "abs":
                raise ValueError(
                    f"the gradient wire needs an 'abs' quantizer stage "
                    f"(per-tensor eb = eb_rel * rms overrides the spec's "
                    f"bound); got {pipe.quant.mode!r} in {self.pipeline!r}")
            if "cap=" not in self.pipeline:
                # a spec that is silent about the outlier cap inherits
                # this config's; an explicit cap= in the spec wins
                pipe = dataclasses.replace(
                    pipe, quant=dataclasses.replace(
                        pipe.quant, cap=self.outlier_cap_frac))
            return pipe
        return Pipeline(QuantStage("abs", 1.0, self.outlier_cap_frac),
                        PackStage(self.bin_bits))

    def qcfg(self):
        return self.pipe().qcfg()


@jax.tree_util.register_pytree_node_class
class CompressedShard:
    """One pod's wire payload — an `Encoded` container plus its (static)
    pipeline and element count.  The arrays inside `enc` are exactly what
    the transport moves; the legacy field names (`words`, `header_words`,
    `payload`, ...) remain as read-only views."""

    def __init__(self, enc: Encoded, pipe: Pipeline, n: int):
        self.enc = enc
        self.pipe = pipe
        self.n = n

    def tree_flatten(self):
        return (self.enc,), (self.pipe, self.n)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    # --- legacy field views ------------------------------------------------
    @property
    def words(self):
        """The §4 packed bin plane.  For a staged pipeline this decodes
        the word stages (exact inverses), so it is the same bit-identical
        plane a stage-free pipeline would ship — except under a pred
        chain (§9), where the plane holds the folded residual codes (the
        pred inverse lives bin-side, in `Pipeline.decode`)."""
        if self.pipe.stages:
            return self.pipe.decode_words(self.enc.headers,
                                          self.enc.payload,
                                          self.pipe.n_words(self.n))
        return self.enc.payload

    @property
    def header_words(self):
        """The first non-empty stage header plane (the chunk coder's
        width codes)."""
        for h in self.enc.headers:
            if h.size:
                return h
        raise AttributeError(
            f"pipeline {self.pipe.spec()!r} has no header planes")

    @property
    def payload(self):
        return self.enc.payload

    @property
    def payload_len(self):
        return self.enc.payload_len

    @property
    def out_idx(self):
        return self.enc.out_idx

    @property
    def out_payload(self):
        return self.enc.out_payload

    @property
    def eb(self):
        return self.enc.eb

    @property
    def n_outliers(self):
        return self.enc.n_outliers

    # --- accounting --------------------------------------------------------
    def nbytes(self):
        """Measured per-pod transmitted footprint of one all-gather: a
        static int for static chains, traced (data-dependent) with a
        length-variable lossless stage.  Routed through the single
        accounting accessor `core.transport.wire_bytes` (DESIGN.md §8)."""
        return _wire_bytes(self)

    def capacity_nbytes(self) -> int:
        """Static upper bound — what the padded all-gather buffer holds."""
        return self.pipe.capacity_bytes(self.enc)


def compress_shard(g: jnp.ndarray, cfg: GradCompressionConfig,
                   *, integrity: bool = False):
    """Run one pod-local gradient through the compression pipeline.
    Returns (CompressedShard, Quantized) — the second carries the local
    outlier/recon planes (residual bookkeeping); only the shard's arrays
    go on the wire.  `integrity=True` attaches the §12 wire checksum
    (an extra aux plane — the transmitted planes are unchanged)."""
    pipe = cfg.pipe()
    with obs.scope("grads.compress"):
        flat = g.reshape(-1).astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(flat * flat))
        eb = jnp.asarray(cfg.eb_rel, jnp.float32) * rms
        enc, q = pipe.encode(flat, eb=eb, return_quantized=True,
                             integrity=integrity)
    return CompressedShard(enc, pipe, flat.size), q


def _psum_fallback(flat, axis):
    """The overflow branch of the compressed mean: the raw f32 sum."""
    with obs.scope("transport.psum_fallback"):
        return jax.lax.psum(flat, axis)


def compressed_mean(g: jnp.ndarray, cfg: GradCompressionConfig, axis: str,
                    *, transport: Transport | None = None,
                    integrity: str | None = None):
    """Compressed mean of g over the `axis` collective (call inside
    shard_map).  Returns (mean, residual) — residual is THIS shard's
    error-feedback term, elementwise bounded by eb.  All wire movement
    goes through the Transport layer (DESIGN.md §8); `transport=`
    overrides the default (e.g. Transport(reduce='gather') to pin the
    reference path).

    `integrity='drop'` (§12): every shard ships with its checksum, the
    reduce takes the gather path, and a shard whose received wire fails
    the check is DROPPED from the mean — the sum renormalizes by the
    count of shards that verified, so one corrupt wire degrades the
    mean's sample count instead of poisoning every parameter.  The
    residual contract is unchanged (it describes what THIS shard
    shipped; corruption is a transient fault, not a steady state).
    `integrity='raise'` is not expressible in-graph — decode-side raise
    policies live at the eager call sites (`Pipeline.decode(verify=)`,
    `Transport.all_gather(verify='raise')`)."""
    if integrity not in (None, "drop"):
        raise ValueError(f"integrity must be None or 'drop' in-graph, "
                         f"got {integrity!r} (DESIGN.md §12)")
    tp = TRANSPORT if transport is None else transport
    flat = g.reshape(-1).astype(jnp.float32)
    shard, q = compress_shard(g, cfg, integrity=integrity is not None)
    # all pods must take the same branch: agree by pmax
    any_overflow = jax.lax.pmax(shard.enc.overflow.astype(jnp.int32),
                                axis) > 0
    p = jax.lax.axis_size(axis)

    if integrity == "drop":
        def _verified_mean(_):
            enc_all, ok = tp.all_gather(shard.enc, axis, verify="mask")
            dec = jax.vmap(lambda e: shard.pipe.decode(
                e, n=flat.size, kernels=False))(enc_all)
            w = ok.astype(jnp.float32)
            s = jnp.sum(dec * w[:, None], axis=0)
            return s / jnp.maximum(jnp.sum(w), 1.0)

        mean = jax.lax.cond(
            any_overflow,
            lambda _: _psum_fallback(flat, axis) / p,
            _verified_mean, None)
    else:
        summed = jax.lax.cond(
            any_overflow,
            lambda _: _psum_fallback(flat, axis),
            lambda _: tp.reduce_sum(shard.enc, shard.pipe, flat.size, axis),
            None)
        mean = summed / p
    # residual: what we failed to ship (0 for outliers — they went exact;
    # 0 if the lossless path ran)
    shipped = jnp.where(q.outlier, flat, q.recon)
    resid = jnp.where(any_overflow, 0.0, flat - shipped)
    return mean.reshape(g.shape), resid.reshape(g.shape)


def compressed_mean_tree(grads, residuals, cfg: GradCompressionConfig,
                         axis: str = "pod",
                         transport: Transport | None = None):
    """Tree version with error feedback: grads_in + residuals are
    compressed-averaged; returns (mean_tree, new_residual_tree)."""
    leaves_g, tree = jax.tree.flatten(grads)
    leaves_r = jax.tree.leaves(residuals)
    out_g, out_r = [], []
    for g, r in zip(leaves_g, leaves_r):
        m, nr = compressed_mean(g + r.astype(g.dtype), cfg, axis,
                                transport=transport)
        out_g.append(m.astype(g.dtype))
        out_r.append(nr)
    return jax.tree.unflatten(tree, out_g), jax.tree.unflatten(tree, out_r)


def wire_bytes(n_elems: int, cfg: GradCompressionConfig) -> int:
    """Analytic PACKED wire footprint per pod per tensor — matches
    CompressedShard.nbytes() for a stage-free pipeline (packed uint32
    words + capped (idx, payload) table + header).  With lossless stages
    the footprint becomes data-dependent and this is its upper bound
    (modulo the small header planes); use shard.nbytes() for the
    measured size."""
    pipe = cfg.pipe()
    qc = pipe.qcfg()
    n_words = pipe.n_words(n_elems)
    k = qc.outlier_cap(n_elems)
    return n_words * 4 + k * 8 + 8
