"""Continuous-batching decode engine (DESIGN.md §10): the acceptance pins.

  * Continuous batching with slot churn produces logits/tokens
    bit-identical to the sequential single-request `serve_step` path —
    the engine's vmapped step, slot insertion through the §7/§9 pack/
    unpack inverses, and evict→insert preemption may not move one bit.
  * Closed pages cross any boundary only as `PackedKV` wires, accounted
    through `Transport.bytes_moved` (prefill hand-off, eviction, and the
    per-page streaming-migration ledger on a real 2-device mesh).
  * `slice_pages`/`paste_pages` (the streaming unit) roundtrip exactly.
  * The committed BENCH_decode.json artifact carries the tokens/s,
    ms/step, and wire-vs-raw columns the perf trajectory is tracked by.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compression import kv as KVC
from repro.configs.base import ArchConfig
from repro.core.transport import TRANSPORT
from repro.models import build
from repro.models import engine as E
from repro.models import serve as S

REPO = Path(__file__).resolve().parent.parent
RNG = np.random.default_rng(97)

TINY = ArchConfig(name="tiny-engine", family="dense", n_layers=2,
                  d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=512,
                  head_dim=16)
SEQ = 256       # 2 pages at PAGE=128


@pytest.fixture(scope="module")
def tiny():
    """One compiled tiny model + single-request reference step, shared by
    every in-process engine test (compile once, not per test)."""
    bundle = build(TINY)
    params = bundle.init(jax.random.PRNGKey(0))
    kv_cfg = KVC.kv_quantizer_config()
    step = jax.jit(lambda p, c, t, i: S.serve_step(TINY, p, c, t, i, None,
                                                   kv_cfg))
    return TINY, params, kv_cfg, step


def _prompt(n):
    return RNG.integers(0, TINY.vocab, size=n).astype(np.int32)


def _ref_decode(cfg, params, step, prompt, n_new, seq=SEQ):
    """Sequential batch-1 serve_step greedy decode — THE reference path.
    Returns (tokens, logits per generated position, final cache, pos)."""
    cache = S.make_quant_cache(cfg, 1, seq)
    logits = None
    for i, t in enumerate(prompt):
        logits, cache = step(params, cache, jnp.asarray(t).reshape(1, 1),
                             jnp.int32(i))
    toks, logs = [int(jnp.argmax(logits, -1).reshape(()))], [logits]
    pos = len(prompt)
    while len(toks) < n_new:
        logits, cache = step(params, cache,
                             jnp.asarray(toks[-1]).reshape(1, 1),
                             jnp.int32(pos))
        pos += 1
        toks.append(int(jnp.argmax(logits, -1).reshape(())))
        logs.append(logits)
    return toks, logs, cache, pos


def test_slice_paste_pages_roundtrip():
    """slice_pages -> pack -> unpack -> paste_pages restores every page of
    a quantized cache bit-exactly — the streaming-migration unit."""
    x = RNG.standard_normal((2, 3, SEQ, 16)).astype(np.float32)
    q = KVC.quantize_kv(jnp.asarray(x), KVC.kv_quantizer_config())
    empty = jax.tree.map(jnp.zeros_like, q)
    rebuilt = empty._replace(out_idx=jnp.full_like(q.out_idx, -1))
    for p in range(SEQ // S.PAGE):
        page = KVC.slice_pages(q, p)
        wire = KVC.pack_kv(page, stages="zero")
        back = KVC.unpack_kv(wire)
        for a, b in zip(back, page):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        rebuilt = KVC.paste_pages(rebuilt, back, p)
    for a, b in zip(rebuilt, q):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_continuous_batching_matches_sequential_serve_step(tiny):
    """Slot churn through the reference scheduler: more requests than
    slots, staggered lengths, greedy tokens must match the sequential
    single-request serve_step decode for every request."""
    cfg, params, kv_cfg, step = tiny
    prompts = [_prompt(130), _prompt(17), _prompt(140)]
    eng = E.DecodeEngine(cfg, params, n_slots=2, seq=SEQ, kv_cfg=kv_cfg)
    out = eng.run(prompts, max_new_tokens=5)
    assert eng.stats()["inserts"] == 3          # 3 requests over 2 slots
    for rid, prompt in enumerate(prompts):
        ref, _, _, _ = _ref_decode(cfg, params, step, prompt, 5)
        assert out[rid] == ref, f"request {rid} diverged from serve_step"


def test_generate_step_logits_bit_identical_per_slot(tiny):
    """Drive the engine by hand (allocate/prefill/insert/generate_step)
    and compare per-slot logits bit-for-bit against the single-request
    path at every step, across a page boundary."""
    cfg, params, kv_cfg, step = tiny
    prompts = [_prompt(126), _prompt(40)]
    n_new = 4
    eng = E.DecodeEngine(cfg, params, n_slots=2, seq=SEQ, kv_cfg=kv_cfg)
    slots = {}
    for rid, prompt in enumerate(prompts):
        slot = eng.allocate()
        pre = eng.prefill(prompt)
        assert isinstance(pre.pages.k, KVC.PackedKV)
        eng.insert(slot, pre)
        slots[rid] = slot
    got = {rid: [] for rid in slots}
    for _ in range(n_new - 1):       # first token came from prefill
        logits, _ = eng.generate_step()
        for rid, slot in slots.items():
            got[rid].append(np.asarray(logits[slot]))
    for rid, prompt in enumerate(prompts):
        _, ref_logs, _, _ = _ref_decode(cfg, params, step, prompt, n_new)
        for k, mine in enumerate(got[rid]):
            ref = np.asarray(ref_logs[k + 1][0])
            np.testing.assert_array_equal(mine, ref)


def test_evict_insert_churn_is_bit_transparent(tiny):
    """Preemption: step a request, evict it to the PackedCache wire,
    re-insert into a DIFFERENT engine/slot, keep stepping — logits stay
    bit-identical to the uninterrupted single-request path, and both
    hand-offs are accounted as wires."""
    cfg, params, kv_cfg, step = tiny
    prompt = _prompt(130)
    eng = E.DecodeEngine(cfg, params, n_slots=2, seq=SEQ, kv_cfg=kv_cfg)
    pre = eng.prefill(prompt)
    eng.insert(0, pre)
    l1, _ = eng.generate_step()
    moved = eng.evict(0)
    assert isinstance(moved.pages.k, KVC.PackedKV)
    assert eng.allocate() == 0                  # the slot was freed
    eng2 = E.DecodeEngine(cfg, params, n_slots=2, seq=SEQ, kv_cfg=kv_cfg)
    eng2.insert(1, moved)
    l2, _ = eng2.generate_step()
    _, ref_logs, _, _ = _ref_decode(cfg, params, step, prompt, 3)
    np.testing.assert_array_equal(np.asarray(l1[0]),
                                  np.asarray(ref_logs[1][0]))
    np.testing.assert_array_equal(np.asarray(l2[1]),
                                  np.asarray(ref_logs[2][0]))
    # every hand-off went through bytes_moved accounting
    cross = lambda w: float(TRANSPORT.bytes_moved(w, op="send_pages"))
    assert eng.stats()["wire_bytes"] == cross(pre.pages) + cross(moved.pages)
    assert eng2.stats()["wire_bytes"] == cross(moved.pages)


def test_wire_accounting_matches_bytes_moved_and_beats_raw(tiny):
    """stats()['wire_bytes'] is exactly Transport.bytes_moved of the
    wires that crossed, and the per-slot wire stays below the raw-bf16
    slot footprint (the §10 claim the bench reports)."""
    cfg, params, kv_cfg, _ = tiny
    eng = E.DecodeEngine(cfg, params, n_slots=1, seq=SEQ, kv_cfg=kv_cfg)
    pre = eng.prefill(_prompt(140))
    expect = float(TRANSPORT.bytes_moved(pre.pages, op="send_pages"))
    eng.insert(0, pre)
    assert eng.stats()["wire_bytes"] == expect
    assert expect < eng.raw_slot_bytes()


def test_insert_refuses_live_slot_and_raw_planes(tiny):
    cfg, params, kv_cfg, _ = tiny
    eng = E.DecodeEngine(cfg, params, n_slots=1, seq=SEQ, kv_cfg=kv_cfg)
    pre = eng.prefill(_prompt(9))
    eng.insert(0, pre)
    with pytest.raises(AssertionError):
        eng.insert(0, pre)                      # live slot
    eng.release(0)
    raw = pre._replace(pages=pre.pages._replace(k=pre.pages.hot_k))
    with pytest.raises(AssertionError):
        eng.insert(0, raw)                      # raw plane is not a wire


def test_kv_page_chain_presets_resolve():
    """The engine page-chain presets split under the two-domain grammar
    (§9 fragments applied per page) and pack a page cleanly."""
    from repro.configs.registry import KV_PAGE_CHAINS, get_kv_chain

    for name in KV_PAGE_CHAINS:
        spec = get_kv_chain(name)
        pred, words = KVC._page_stages(spec)
        assert all(hasattr(p, "encode_bins") for p in pred)
        x = RNG.standard_normal((1, 1, S.PAGE, 16)).astype(np.float32)
        q = KVC.quantize_kv(jnp.asarray(x), KVC.kv_quantizer_config(),
                            page=S.PAGE)
        back = KVC.unpack_kv(KVC.pack_kv(q, page=S.PAGE, stages=spec),
                             page=S.PAGE)
        for a, b in zip(back, q):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bench_decode_artifact_is_committed():
    """BENCH_decode.json (the perf trajectory's first point) must exist,
    parse, and carry the tokens/s, ms/step, and wire-vs-raw columns in
    the roofline rows format."""
    path = REPO / "BENCH_decode.json"
    assert path.exists(), "BENCH_decode.json missing (benchmarks/" \
                          "engine_bench.py --smoke writes it)"
    rows = json.loads(path.read_text())
    assert isinstance(rows, list) and rows
    for row in rows:
        for key in ("bench", "arch", "n_slots", "seq", "tokens_per_s",
                    "ms_per_step", "wire_bytes_per_slot",
                    "raw_bf16_bytes_per_slot", "wire_vs_raw"):
            assert key in row, (key, sorted(row))
        assert row["tokens_per_s"] > 0
        assert row["ms_per_step"] > 0
        assert row["wire_bytes_per_slot"] < row["raw_bf16_bytes_per_slot"]


# ------------------------------------------- 2-device streaming migration ---

ENGINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.compression import kv as KVC
    from repro.configs.base import ArchConfig
    from repro.core.transport import TRANSPORT
    from repro.models import build
    from repro.models import engine as E
    from repro.models import serve as S

    cfg = ArchConfig(name="tiny-engine", family="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                     vocab=512, head_dim=16)
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    SEQ = 256
    mesh = jax.make_mesh((2,), ("wire",))
    rng = np.random.default_rng(11)
    kv_cfg = KVC.kv_quantizer_config()
    step = jax.jit(lambda p, c, t, i: S.serve_step(cfg, p, c, t, i, None,
                                                   kv_cfg))

    def ref_decode(prompt, n_new):
        cache = S.make_quant_cache(cfg, 1, SEQ)
        logits = None
        for i, t in enumerate(prompt):
            logits, cache = step(params, cache,
                                 jnp.asarray(t).reshape(1, 1), jnp.int32(i))
        toks, logs = [int(jnp.argmax(logits, -1).reshape(()))], [logits]
        pos = len(prompt)
        while len(toks) < n_new:
            logits, cache = step(params, cache,
                                 jnp.asarray(toks[-1]).reshape(1, 1),
                                 jnp.int32(pos))
            pos += 1
            toks.append(int(jnp.argmax(logits, -1).reshape(())))
            logs.append(logits)
        return toks, logs, cache

    # prefill host = rank 0, decode host = rank 1: requests stream page
    # by page through Transport.send_pages while prefill continues
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (140, 135, 20)]
    eng = E.DecodeEngine(cfg, params, n_slots=2, seq=SEQ, kv_cfg=kv_cfg)

    def admit(slot, rid):
        sp = E.stream_prefill(cfg, params, prompts[rid], seq=SEQ,
                              mesh=mesh, axis="wire", src=0, dst=1,
                              kv_cfg=kv_cfg, stages="zero")
        # closed pages moved ONLY as PackedKV wires: re-derive each ledger
        # entry from an independent pack of the (bit-identical) received
        # pages and Transport.bytes_moved — the numbers must agree exactly
        for kind, p, nbytes in sp.stats["ledger"]:
            if kind != "PageWire":
                continue
            assert nbytes == float(TRANSPORT.bytes_moved(
                E.PageWire(
                    KVC.pack_kv(KVC.slice_pages(sp.cache.k, p),
                                stages="zero"),
                    KVC.pack_kv(KVC.slice_pages(sp.cache.v, p),
                                stages="zero")),
                op="send_pages")), (kind, p)
        n_closed = len(prompts[rid]) // S.PAGE
        assert sp.stats["pages_streamed"] == n_closed, sp.stats
        eng.insert_cache(slot, sp.cache, next_token=sp.next_token,
                         pos=sp.pos, request=rid)
        return [int(sp.next_token.reshape(()))]

    N_NEW = 4
    refs = {rid: ref_decode(p, N_NEW) for rid, p in enumerate(prompts)}
    got = {0: admit(0, 0), 1: admit(1, 1)}
    live = {0: 0, 1: 1}                       # slot -> rid
    print("STREAM_OK")

    churned = False
    while live:
        logits, toks = eng.generate_step()
        toks = np.asarray(toks)
        for slot, rid in list(live.items()):
            got[rid].append(int(toks[slot]))
            np.testing.assert_array_equal(
                np.asarray(logits[slot]),
                np.asarray(refs[rid][1][len(got[rid]) - 1][0]))
            if len(got[rid]) >= N_NEW:
                eng.release(slot)             # slot churn:
                del live[slot]
                if not churned:               # admit request 2 mid-flight
                    churned = True
                    got[2] = admit(slot, 2)
                    live[slot] = 2
    for rid in range(3):
        assert got[rid] == refs[rid][0], (rid, got[rid], refs[rid][0])
    print("CHURN_OK")
    print("BIT_IDENTICAL_OK")
""")


@pytest.mark.slow
def test_streaming_migration_engine_two_devices():
    """Acceptance: on a 2-device mesh, continuous batching with slot
    churn + per-page streaming migration produces logits bit-identical
    to sequential serve_step, and closed pages move only as PackedKV
    wires (each ledger entry re-derived through Transport.bytes_moved)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", ENGINE_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=str(REPO))
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("STREAM_OK", "CHURN_OK", "BIT_IDENTICAL_OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr)


def test_record_audit_folds_into_stats(tiny):
    """§12 observability: `record_audit` accumulates AuditReports into
    the cumulative audit_* counters of stats(), mirroring
    train_loop.AuditCounters on the serving side."""
    from repro.core.audit import AuditReport

    def rep(violations=0, nonfinite=0, overflow=0, max_err=0.0):
        return AuditReport(n=jnp.int32(64), violations=jnp.int32(violations),
                           max_err=jnp.float32(max_err),
                           n_nonfinite=jnp.int32(nonfinite),
                           n_outliers=jnp.int32(0),
                           overflow=jnp.asarray(bool(overflow)))

    cfg, params, kv_cfg, _ = tiny
    eng = E.DecodeEngine(cfg, params, n_slots=1, seq=256, kv_cfg=kv_cfg)
    st = eng.stats()
    assert st["audit_reports"] == 0 and st["audit_violations"] == 0

    eng.record_audit(rep(max_err=1e-4))
    eng.record_audit([rep(violations=1, max_err=5e-4), None,
                      rep(nonfinite=2, overflow=1)])
    st = eng.stats()
    assert st["audit_reports"] == 3
    assert st["audit_violations"] == 1
    assert st["audit_nonfinite"] == 2
    assert st["audit_overflow"] == 1
    assert st["audit_max_err"] == pytest.approx(5e-4)
