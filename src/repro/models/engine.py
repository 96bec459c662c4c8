"""Continuous-batching decode engine over the quantized KV cache
(DESIGN.md §10).

`models/serve.py` gives one aligned-batch decode step; production serving
is a slot machine: requests arrive at different times, prefill on another
host, and their pages migrate into decode slots mid-flight.  This module
drives the wire primitives (`PackedKV`, `PackedCache`,
`Transport.send_pages`) at request rate, in the style of MaxText's decode
microbenchmark:

    engine = DecodeEngine(cfg, params, n_slots=8, seq=2048)
    pre    = engine.prefill(prompt)        # -> pages (PackedCache wire)
    slot   = engine.allocate()
    engine.insert(slot, pre)               # decode through §7/§9 inverses
    logits, tokens = engine.generate_step()  # one batched step, all slots

Slot/page lifecycle: **allocate** (claim a free slot) → **fill** (each
step writes the slot's open hot page) → **close** (the filled page
quantizes in-step — serve.py's lax.cond) → **evict** (pack the slot back
to a `PackedCache` wire and free it: preemption / decode-host
rebalancing).  Closed pages cross any boundary ONLY as `PackedKV` wires:
`prefill` hands over a `PackedCache`, `evict` emits one, and streaming
migration ships single-page `PageWire`s — `stats()["wire_bytes"]`
accounts every transfer through `Transport.bytes_moved`, and nothing in
the engine ever moves a dequantized plane.

Bit-identity: every slot is a batch-1 `QuantCache` stacked on a leading
slot axis, and `generate_step` is `jax.vmap(serve_step)` over that axis
with per-slot positions.  Insertion decodes through the exact pack/unpack
inverses, so each slot's cache is bit-identical to the single-request
path's, through evict → insert churn and cross-host migration.  Its
logits match the batch-1 `serve_step` bit for bit only where the
backend's matmuls do not depend on the batch size (DESIGN.md §10).

Streaming migration (`stream_prefill`): on the prefill host each page is
packed and handed to `Transport.send_pages` the moment it closes, while
the host keeps enqueueing prefill steps — dispatch is async and the
page-p send has no data dependency on the page-p+1 compute, so the
transfer overlaps ongoing prefill instead of serializing behind a
monolithic end-of-prompt `transfer_cache`.  The open hot page rides raw
in the final tail send (it is not quantized yet — the serve.py §8
contract); every closed page crosses as a `PackedKV` wire.
"""
from __future__ import annotations

import collections
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ArchConfig
from repro.core import QuantizerConfig
from repro.core import audit as A
from repro.core.transport import TRANSPORT, Transport
from repro.compression import kv as KVC
from . import serve as S


class PageWire(NamedTuple):
    """One closed page on the wire: the K and V `PackedKV` slices for a
    single page — the unit of streaming migration (DESIGN.md §10)."""
    k: KVC.PackedKV
    v: KVC.PackedKV


class TailWire(NamedTuple):
    """The end-of-prefill remainder: the open hot page (raw by the §8
    contract — not quantized yet) plus the last prompt-position logits the
    decode host needs to pick the first generated token."""
    hot_k: jnp.ndarray
    hot_v: jnp.ndarray
    logits: jnp.ndarray


class PrefillResult(NamedTuple):
    """What `prefill`/`evict` hand to `insert`: closed pages as `PackedKV`
    wires inside a `PackedCache`, the next token to feed, and the insert
    position.  `logits` is the last computed position's logits (None on
    evict — the token is already chosen)."""
    pages: S.PackedCache
    next_token: jnp.ndarray          # int32 [1, 1]
    logits: Optional[jnp.ndarray]    # f32 [1, V]
    pos: int                         # next write position


class StreamedPrefill(NamedTuple):
    """`stream_prefill` result on the decode host: the slot cache
    assembled from per-page wires (use `DecodeEngine.insert_cache`), the
    first token, the insert position, and the transfer ledger."""
    cache: S.QuantCache              # batch-1, bit-identical to the source
    next_token: jnp.ndarray          # int32 [1, 1]
    logits: jnp.ndarray              # f32 [1, V]
    pos: int
    stats: dict


class DecodeEngine:
    """Continuous-batching decode over `n_slots` independent requests at
    per-slot positions, each slot a batch-1 quantized cache (DESIGN.md
    §10).  Host-side slot table; device state advances through one
    vmapped `serve_step` per `generate_step` call.

    `stages` is the per-page chain every boundary wire uses (a
    `KV_PAGE_CHAINS` preset value or raw fragment), or "auto"/"auto:SET"
    to let the §11 selector pick per page at page close — `pack_kv`
    resolves it, so prefill/evict/stream_prefill wires all inherit the
    choice and stay self-describing.

    `integrity` (DESIGN.md §12) names a degradation policy
    (`core.audit.DEGRADATION_POLICIES`: "raise" / "rerequest" / a
    registered custom handler).  When set, every boundary wire the
    engine emits carries the §12 checksum and `insert` re-verifies it:
    a clean check bumps `stats()["audit_checks"]`, a failed one bumps
    `audit_failures`, routes through the policy, and — unless the
    policy raised — the insert is refused (returns False) so the
    caller can re-request the pages."""

    def __init__(self, cfg: ArchConfig, params, *, n_slots: int, seq: int,
                 kv_cfg: QuantizerConfig | None = None, stages="zero",
                 transport: Transport | None = None,
                 integrity: str | None = None):
        assert seq % S.PAGE == 0, (seq, S.PAGE)
        assert cfg.family != "hybrid", "engine serves the QuantCache path"
        self.cfg, self.params = cfg, params
        self.n_slots, self.seq = int(n_slots), int(seq)
        self.kv_cfg = (KVC.kv_quantizer_config() if kv_cfg is None
                       else kv_cfg)
        self.stages = stages
        self.integrity = integrity
        if integrity is not None:
            A.get_policy(integrity)          # fail fast on unknown names
        self.transport = TRANSPORT if transport is None else transport
        one = S.make_quant_cache(cfg, 1, seq)
        self._cache = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.n_slots,) + x.shape), one)
        self._pos = jnp.zeros((self.n_slots,), jnp.int32)
        self._tok = jnp.zeros((self.n_slots, 1, 1), jnp.int32)
        self.requests: list = [None] * self.n_slots   # host-side slot table
        self._stats = dict(prefill_tokens=0, generated_tokens=0, steps=0,
                           wire_bytes=0.0, inserts=0,
                           audit_checks=0, audit_failures=0,
                           audit_reports=0, audit_violations=0,
                           audit_nonfinite=0, audit_overflow=0,
                           audit_max_err=0.0)
        self._slot_audit = [dict(checks=0, failures=0)
                            for _ in range(self.n_slots)]
        self._step1 = jax.jit(self._one_step)
        self._vstep = jax.jit(self._slots_step)

    # --- jitted programs --------------------------------------------------

    def _one_step(self, params, cache, tok, pos):
        """The single-request serve path — the bit-identity reference."""
        return S.serve_step(self.cfg, params, cache, tok, pos, None,
                            self.kv_cfg)

    def _slots_step(self, params, cache, tok, pos, live):
        """vmap the batch-1 serve_step over the slot axis; freeze dead
        slots (their cache/pos/token must not drift while free)."""
        logits, new = jax.vmap(
            self._one_step, in_axes=(None, 0, 0, 0))(params, cache, tok, pos)
        with obs.scope("serve.slots"):
            keep = lambda n, o: jnp.where(
                live.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
            new = jax.tree.map(keep, new, cache)
            nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
            tok = jnp.where(live, nxt, tok[:, 0, 0]).reshape(-1, 1, 1)
            pos = jnp.where(live, pos + 1, pos)
        return logits[:, 0], tok, pos, new

    # --- slot lifecycle ---------------------------------------------------

    def allocate(self) -> Optional[int]:
        """Claim a free slot (lifecycle step 1), or None when saturated."""
        for slot in range(self.n_slots):
            if self.requests[slot] is None:
                return slot
        return None

    def prefill(self, prompt, *, request=None) -> PrefillResult:
        """Run one request's prompt through the batch-1 `serve_step` chain
        and emit the slot-insert wire: closed pages leave as `PackedKV`
        (per-page chain `self.stages`), the open hot page rides raw.
        `request` only labels the `engine.prefill` span."""
        prompt = jnp.asarray(prompt, jnp.int32).reshape(-1)
        m = int(prompt.shape[0])
        assert 0 < m < self.seq, (m, self.seq)
        with obs.span("engine.prefill", request=request, tokens=m):
            cache = S.make_quant_cache(self.cfg, 1, self.seq)
            logits = None
            with obs.span("engine.prefill.tokens", request=request):
                for i in range(m):
                    logits, cache = self._step1(self.params, cache,
                                                prompt[i].reshape(1, 1),
                                                jnp.int32(i))
            with obs.span("engine.prefill.pack", request=request):
                nxt = jnp.argmax(logits, -1).astype(jnp.int32).reshape(1, 1)
                wire = self._seal(S.pack_cache(cache, stages=self.stages))
        self._stats["prefill_tokens"] += m
        return PrefillResult(wire, nxt, logits, m)

    def _seal(self, wire: S.PackedCache) -> S.PackedCache:
        """Attach the §12 checksum to both wire planes (integrity on)."""
        if self.integrity is None:
            return wire
        return wire._replace(k=A.attach_checksum(wire.k),
                             v=A.attach_checksum(wire.v))

    def _verify_pages(self, slot: int, pages: S.PackedCache) -> bool:
        """§12 receive-side check: re-verify any carried checksum on the
        K/V wire planes.  Clean → True.  On mismatch the failure is
        counted (engine-wide and per slot) and routed through the
        configured degradation policy; returns False unless the policy
        raised."""
        ok = True
        for name, plane in (("k", pages.k), ("v", pages.v)):
            if not A.has_checksum(plane):
                continue
            self._stats["audit_checks"] += 1
            self._slot_audit[slot]["checks"] += 1
            if bool(A.verify_wire(plane)):
                continue
            ok = False
            self._stats["audit_failures"] += 1
            self._slot_audit[slot]["failures"] += 1
            A.get_policy(self.integrity or "raise")(dict(
                site="engine.insert", slot=slot, plane=name,
                what="PackedCache"))
        return ok

    def insert(self, slot: int, pre: PrefillResult, *, request=True) -> bool:
        """Insert a prefilled/evicted request into `slot`.  The wire
        decodes through the exact §7/§9 page-chain inverses
        (`unpack_cache`), so the slot history is bit-identical to the
        source cache and subsequent logits are bit-identical to the
        single-request path.  Accounts the wire via
        `Transport.bytes_moved(op='send_pages')`.

        Returns True on success.  With checksummed wires (§12), a failed
        check routes through the `integrity` policy first; if it returns
        (rerequest-style policies), the slot is left free and this
        returns False so the caller can fetch the pages again."""
        assert self.requests[slot] is None, f"slot {slot} is live"
        assert isinstance(pre.pages.k, KVC.PackedKV), type(pre.pages.k)
        assert isinstance(pre.pages.v, KVC.PackedKV), type(pre.pages.v)
        # `request=True` is the slot table's plain marker, not an id
        rid = None if isinstance(request, bool) else request
        with obs.span("engine.insert", slot=slot, request=rid):
            self._account(pre.pages)
            with obs.span("engine.insert.verify", slot=slot, request=rid):
                if not self._verify_pages(slot, pre.pages):
                    return False
            with obs.span("engine.insert.unpack", slot=slot, request=rid):
                self.insert_cache(slot, S.unpack_cache(pre.pages),
                                  next_token=pre.next_token, pos=pre.pos,
                                  request=request)
        return True

    def insert_cache(self, slot: int, cache1: S.QuantCache, *,
                     next_token, pos: int, request=True):
        """Landing-side insert of an already-decoded batch-1 cache (the
        streaming-migration path: its pages arrived one `PageWire` at a
        time and were assembled with `paste_pages`)."""
        assert self.requests[slot] is None, f"slot {slot} is live"
        self._cache = jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_slice(
                full, one[None].astype(full.dtype),
                (slot,) + (0,) * one.ndim),
            self._cache, cache1)
        self._pos = self._pos.at[slot].set(pos)
        self._tok = self._tok.at[slot].set(
            jnp.asarray(next_token, jnp.int32).reshape(1, 1))
        self.requests[slot] = request
        self._stats["inserts"] += 1

    def generate_step(self):
        """One batched decode step over every live slot (lifecycle step 2:
        fill — and, on page boundaries, step 3: close).  Returns
        (logits f32 [n_slots, V], tokens int32 [n_slots]); dead-slot rows
        are stale and must be ignored by the caller."""
        live = [r is not None for r in self.requests]
        if not any(live):
            raise RuntimeError("generate_step with no live slot")
        with obs.span("engine.generate_step", live=sum(live)):
            with obs.span("engine.step.slot_check"):
                for slot, on in enumerate(live):
                    assert not on or int(self._pos[slot]) < self.seq, (
                        f"slot {slot} ran past seq={self.seq}; release it "
                        f"first")
                live_mask = jnp.asarray(live)
            with obs.span("engine.step.dispatch"):
                logits, self._tok, self._pos, self._cache = self._vstep(
                    self.params, self._cache, self._tok, self._pos,
                    live_mask)
        self._stats["steps"] += 1
        self._stats["generated_tokens"] += sum(live)
        return logits, self._tok[:, 0, 0]

    def evict(self, slot: int) -> PrefillResult:
        """Pack `slot` back to the `PackedCache` wire (lifecycle step 4 —
        preemption / rebalancing) and free it.  The result re-`insert`s
        into any engine bit-exactly."""
        assert self.requests[slot] is not None, f"slot {slot} is free"
        with obs.span("engine.evict", slot=slot):
            cache1 = jax.tree.map(lambda full: full[slot], self._cache)
            wire = self._seal(S.pack_cache(cache1, stages=self.stages))
            out = PrefillResult(wire, self._tok[slot], None,
                                int(self._pos[slot]))
        self._account(wire)
        self.release(slot)
        return out

    def release(self, slot: int):
        """Free a slot without packing (request finished)."""
        self.requests[slot] = None

    # --- accounting -------------------------------------------------------

    def _account(self, wire):
        moved = float(self.transport.bytes_moved(wire, op="send_pages"))
        self._stats["wire_bytes"] += moved
        return moved

    def raw_slot_bytes(self) -> int:
        """bf16 K+V footprint of ONE slot's history at full `seq` — the
        wire-bytes-vs-raw denominator every report uses."""
        g, hd = self.cfg.n_kv_heads, self.cfg.head_dim
        return 2 * self.cfg.n_layers * self.seq * g * hd * 2

    def record_audit(self, report) -> None:
        """Fold a §12 `AuditReport` (or a list of them — the per-layer
        shape quantize-side callers produce with verify=True) into the
        engine's cumulative audit_* counters, surfaced by `stats()`.
        Mirrors `train_loop.AuditCounters` on the training side, so both
        runtimes report run-level bound violations the same way."""
        # AuditReport IS a NamedTuple — dispatch on the counter field,
        # not on tuple-ness, to tell one report from a list of them
        for rep in (report,) if hasattr(report, "violations") else report:
            if rep is None:
                continue
            self._stats["audit_reports"] += 1
            self._stats["audit_violations"] += int(rep.violations)
            self._stats["audit_nonfinite"] += int(rep.n_nonfinite)
            self._stats["audit_overflow"] += int(rep.overflow)
            self._stats["audit_max_err"] = max(
                self._stats["audit_max_err"], float(rep.max_err))

    def stats(self) -> dict:
        out = dict(self._stats)
        out["slot_audit"] = [dict(d) for d in self._slot_audit]
        return out

    # --- reference scheduler ----------------------------------------------

    def run(self, prompts, max_new_tokens: int, *, prefill_fn=None):
        """Reference continuous-batching loop: admit pending requests as
        slots free (churn), step every live slot, release finished ones.
        `prefill_fn(prompt)` may return a `PrefillResult` (local prefill,
        the default `self.prefill`) or a `StreamedPrefill` (pages already
        migrated from another host).  Returns {request index: [generated
        token ids]} — `max_new_tokens` each, greedy."""
        prefill_fn = self.prefill if prefill_fn is None else prefill_fn
        prompts = list(prompts)
        pending = collections.deque(enumerate(prompts))
        out = {rid: [] for rid in range(len(prompts))}
        budget = {}
        while pending or any(r is not None for r in self.requests):
            while pending:
                slot = self.allocate()
                if slot is None:
                    break
                rid, prompt = pending.popleft()
                pre = prefill_fn(prompt)
                if isinstance(pre, StreamedPrefill):
                    self.insert_cache(slot, pre.cache,
                                      next_token=pre.next_token,
                                      pos=pre.pos, request=rid)
                    self._stats["wire_bytes"] += pre.stats["wire_bytes"]
                else:
                    self.insert(slot, pre, request=rid)
                out[rid].append(int(jnp.reshape(pre.next_token, ())))
                budget[rid] = max_new_tokens - 1
                if budget[rid] <= 0:
                    self.release(slot)
            if not any(r is not None for r in self.requests):
                continue
            _, toks = self.generate_step()
            toks = np.asarray(toks)
            for slot, rid in enumerate(list(self.requests)):
                if rid is None:
                    continue
                out[rid].append(int(toks[slot]))
                budget[rid] -= 1
                if budget[rid] <= 0 or int(self._pos[slot]) >= self.seq:
                    self.release(slot)          # slot churn
        return out


# --------------------------------------------------- streaming migration ---

def _block_on(a, rank: int):
    """Rank `rank`'s block of an array stacked over a 1-D mesh axis, as
    the array that already lives on that rank's device (no gather)."""
    (shard,) = [s for s in a.addressable_shards
                if s.index[0].start == rank]
    return shard.data[0]


def stream_prefill(cfg: ArchConfig, params, prompt, *, seq: int, mesh,
                   axis: str, src: int = 0, dst: int = 1,
                   kv_cfg: QuantizerConfig | None = None, stages="zero",
                   transport: Transport | None = None) -> StreamedPrefill:
    """Prefill on mesh rank `src`, shipping each KV page to rank `dst`
    the moment it closes (DESIGN.md §10).  Every closed page crosses the
    link as a single-page `PageWire` (two `PackedKV`s) through
    `Transport.send_pages`; the open hot page and the final-position
    logits follow in one raw `TailWire`.  Sends are dispatched
    asynchronously between prefill steps, so page p's transfer overlaps
    page p+1's compute — slot churn never waits for (and never moves) a
    monolithic raw plane.

    Returns a `StreamedPrefill` whose cache is assembled on `dst` from
    the received wires and is bit-identical to the source cache; its
    `stats` carry the per-wire byte ledger
    (`[(kind, page index, bytes), ...]`, accounted via
    `Transport.bytes_moved`)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = TRANSPORT if transport is None else transport
    kv_cfg = KVC.kv_quantizer_config() if kv_cfg is None else kv_cfg
    prompt = jnp.asarray(prompt, jnp.int32).reshape(-1)
    m = int(prompt.shape[0])
    assert 0 < m < seq, (m, seq)

    devs = list(mesh.devices.flat)
    stacked = NamedSharding(mesh, P(axis))

    def _on_src(a):
        # `a` as rank src's block of a [p, ...] array, the other ranks
        # holding zeros of their own: only the ppermute crosses the link
        blocks = [jax.device_put(a, d)[None] if r == src
                  else jnp.zeros((1,) + a.shape, a.dtype, device=d)
                  for r, d in enumerate(devs)]
        return jax.make_array_from_single_device_arrays(
            (len(devs),) + a.shape, stacked, blocks)

    def _send(wire):
        moved = tp.send_pages(jax.tree.map(lambda a: a[0], wire), src, dst,
                              axis)
        return jax.tree.map(lambda a: a[None], moved)

    send_blocks = jax.jit(jax.shard_map(_send, mesh=mesh, in_specs=P(axis),
                                        out_specs=P(axis), axis_names={axis},
                                        check_vma=False))
    send = lambda wire: send_blocks(jax.tree.map(_on_src, wire))
    take = lambda out: jax.tree.map(lambda a: _block_on(a, dst), out)

    step = jax.jit(lambda p, c, t, i: S.serve_step(cfg, p, c, t, i, None,
                                                   kv_cfg))
    cache = S.make_quant_cache(cfg, 1, seq)
    ledger, inflight = [], []
    logits = None
    with obs.span("engine.stream_prefill", tokens=m, src=src, dst=dst):
        for i in range(m):
            logits, cache = step(params, cache, prompt[i].reshape(1, 1),
                                 jnp.int32(i))
            if (i + 1) % S.PAGE == 0:
                p = i // S.PAGE
                with obs.span("transport.send_pages", page=p):
                    wire = PageWire(
                        KVC.pack_kv(KVC.slice_pages(cache.k, p, page=S.PAGE),
                                    page=S.PAGE, stages=stages),
                        KVC.pack_kv(KVC.slice_pages(cache.v, p, page=S.PAGE),
                                    page=S.PAGE, stages=stages))
                    # async dispatch: this send overlaps the next page's
                    # prefill
                    inflight.append((p, send(wire)))
                ledger.append(("PageWire", p,
                               float(tp.bytes_moved(wire, op="send_pages"))))
        tail = TailWire(cache.hot_k, cache.hot_v, logits)
        with obs.span("transport.send_pages", page=m // S.PAGE):
            got_tail = take(send(tail))
        ledger.append(("TailWire", m // S.PAGE,
                       float(tp.bytes_moved(tail, op="send_pages"))))

    # --- decode host: assemble the slot cache from the received wires ---
    recv = S.make_quant_cache(cfg, 1, seq)
    k, v = recv.k, recv.v
    for p, got in inflight:
        w = take(got)
        k = KVC.paste_pages(k, KVC.unpack_kv(w.k, page=S.PAGE), p,
                            page=S.PAGE)
        v = KVC.paste_pages(v, KVC.unpack_kv(w.v, page=S.PAGE), p,
                            page=S.PAGE)
    assembled = S.QuantCache(k, v, got_tail.hot_k, got_tail.hot_v)
    nxt = jnp.argmax(got_tail.logits, -1).astype(jnp.int32).reshape(1, 1)
    stats = dict(wire_bytes=sum(b for *_, b in ledger),
                 pages_streamed=len(inflight), ledger=ledger,
                 prefill_tokens=m)
    return StreamedPrefill(assembled, nxt, got_tail.logits, m, stats)
