"""ptc-share speculative decoding: draft-propose / one-wave verify with
greedy accept, page-table rollback, and BIT-IDENTICAL outputs vs the
non-speculative sequential decode regardless of draft quality."""
import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu.serve import (InferenceEngine, PagedLM, PagedLMConfig,
                              TenantConfig)

CFG = PagedLMConfig(vocab=32, d=8, page=4, seed=2)


def _reqs(n, seed=0):
    rng = np.random.RandomState(seed)
    return [(list(rng.randint(0, CFG.vocab, size=rng.randint(2, 11))),
             int(rng.randint(3, 8)),
             "hi" if i % 3 == 0 else "lo") for i in range(n)]


def _run(model, reqs, spec_k, spec_draft="self", n_pages=96):
    with pt.Context(nb_workers=2, scheduler="lws") as ctx:
        eng = InferenceEngine(
            ctx, model, n_pages=n_pages, max_seqs=8,
            tenants=[TenantConfig("hi", priority=4, weight=4),
                     TenantConfig("lo")],
            spec_k=spec_k, spec_draft=spec_draft)
        hs = [eng.submit(p, n, t) for p, n, t in reqs]
        eng.run(timeout_s=180)
        stats = dict(eng.stats)
        scope_rows = ctx.stats()["scope"]["tenants"]
        serve_ns = ctx.stats()["serve"]
        eng.close()
    return hs, stats, scope_rows, serve_ns


@pytest.mark.parametrize("k", [2, 4])
def test_spec_oracle_draft_bit_identical_and_accepts(k):
    """spec_draft='self' (the target's own argmax chain): every draft
    accepted, multiple tokens per wave, outputs bit-identical to the
    numpy oracle AND the non-speculative engine."""
    model = PagedLM(CFG)
    reqs = _reqs(6)
    hs, st, rows, serve_ns = _run(model, reqs, spec_k=k)
    h0, _, _, _ = _run(model, reqs, spec_k=0)
    for h, hseq, (p, n, _t) in zip(hs, h0, reqs):
        assert h.state == "done"
        rt, ro = model.reference_generate(p, n)
        assert h.tokens == rt
        assert np.array_equal(np.stack(h.outputs), ro)
        assert h.tokens == hseq.tokens
        assert np.array_equal(np.stack(h.outputs), np.stack(hseq.outputs))
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] == st["spec_proposed"]  # oracle draft
    # fewer decode waves than tokens: speculation actually batched
    total_new = sum(len(h.generated) for h in hs)
    assert st["spec_steps"] < total_new
    # acceptance surfaced per tenant + in the serve namespace
    assert serve_ns["spec"]["accept_rate"] == 1.0
    assert sum(r.get("spec_accepted", 0) for r in rows.values()) == \
        st["spec_accepted"]
    assert any(r.get("spec_accept_pct_count", 0) > 0
               for r in rows.values())


def test_spec_adversarial_draft_still_bit_identical():
    """A draft with UNRELATED weights proposes garbage: acceptance ~0,
    every wave rolls back its rejected tokens, and the output stream is
    STILL bit-identical to sequential decode (the correctness bar)."""
    model = PagedLM(CFG)
    draft = PagedLM(PagedLMConfig(vocab=32, d=8, page=4, seed=909))
    reqs = _reqs(5, seed=3)
    hs, st, _rows, _ = _run(model, reqs, spec_k=3, spec_draft=draft)
    for h, (p, n, _t) in zip(hs, reqs):
        assert h.state == "done"
        rt, ro = model.reference_generate(p, n)
        assert h.tokens == rt
        assert np.array_equal(np.stack(h.outputs), ro)
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] < st["spec_proposed"]


def test_spec_rollback_returns_pages_and_pool_drains():
    """After a full speculative run every page and slot is back: the
    rejected-window rollback leaks nothing."""
    model = PagedLM(CFG)
    draft = PagedLM(PagedLMConfig(vocab=32, d=8, page=4, seed=909))
    with pt.Context(nb_workers=2, scheduler="lws") as ctx:
        eng = InferenceEngine(ctx, model, n_pages=32, max_seqs=4,
                              tenants=[TenantConfig("t")], spec_k=3,
                              spec_draft=draft)
        free0 = eng.pool.free_pages
        hs = [eng.submit([1, 2, 3, 4, 5, 6, 7], 5, "t")
              for _ in range(5)]
        eng.run(timeout_s=120)
        assert all(h.state == "done" for h in hs)
        assert eng.pool.free_pages == free0
        assert len(eng._free_slots) == 4
        st = eng.pool.stats()
        assert st["free"] + st["cached_free"] == st["n_pages"]
        eng.close()


def test_spec_page_shortfall_falls_back_to_plain_decode():
    """A pool too small for the speculative window degrades to normal
    decode (spec_fallbacks counted) instead of stalling or failing."""
    model = PagedLM(CFG)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    with pt.Context(nb_workers=2, scheduler="lws") as ctx:
        eng = InferenceEngine(ctx, model, n_pages=8, max_seqs=2,
                              tenants=[TenantConfig("t")], spec_k=4)
        h = eng.submit(prompt, 6, "t")
        eng.run(timeout_s=120)
        st = dict(eng.stats)
        eng.close()
    assert h.state == "done"
    rt, ro = model.reference_generate(prompt, 6)
    assert h.tokens == rt
    assert np.array_equal(np.stack(h.outputs), ro)
    assert st["spec_fallbacks"] > 0


def test_spec_with_prefix_cache_composes():
    """Both engines on: warm shared-prefix admission + speculative
    decode on the same sequences, still bit-identical."""
    model = PagedLM(CFG)
    common = [5, 9, 2, 11, 7, 1, 8, 6]
    with pt.Context(nb_workers=2, scheduler="lws") as ctx:
        eng = InferenceEngine(ctx, model, n_pages=64, max_seqs=8,
                              tenants=[TenantConfig("a"),
                                       TenantConfig("b")], spec_k=3)
        h1 = eng.submit(common + [3], 5, "a")
        eng.run(timeout_s=120)
        h2 = eng.submit(common + [9], 5, "b")
        h3 = eng.submit(common, 4, "b")
        eng.run(timeout_s=120)
        pool_st = eng.pool.stats()
        eng.close()
    assert pool_st["prefix_hits"] > 0
    for h, (p, n) in ((h1, (common + [3], 5)), (h2, (common + [9], 5)),
                      (h3, (common, 4))):
        rt, ro = model.reference_generate(p, n)
        assert h.tokens == rt
        assert np.array_equal(np.stack(h.outputs), ro)


def test_spec_verify_wave_fuses_on_device():
    """With a TpuDevice attached the homogeneous VATF verify wave runs
    as batched device launches (`batches` / `batched_tasks`), tokens
    identical and outputs allclose to the non-speculative device run (device
    batched-kernel lane bytes are width-dependent, so the DEVICE path
    promises allclose — bit-exactness is the host fold path's
    contract, gated above)."""
    from parsec_tpu.device import TpuDevice
    model = PagedLM(CFG)
    prompts = [[5, 9, 2, 11, 7, 1, 8, 6, 3], [4, 4, 9, 1, 2, 3, 7, 7],
               [1, 2, 8]]

    def run(spec_k):
        with pt.Context(nb_workers=2, scheduler="lws") as ctx:
            dev = TpuDevice(ctx)
            try:
                eng = InferenceEngine(ctx, model, n_pages=64, max_seqs=8,
                                      tenants=[TenantConfig("t")],
                                      dev=dev, spec_k=spec_k)
                hs = [eng.submit(p, 6, "t") for p in prompts]
                eng.run(timeout_s=180)
                ds = dict(dev.stats)
                eng.close()
            finally:
                dev.stop()
        return hs, ds

    hs1, ds1 = run(3)
    hs0, _ = run(0)
    assert ds1["batches"] > 0, ds1
    assert ds1["batched_tasks"] > ds1["batches"], ds1
    for h1, h0 in zip(hs1, hs0):
        assert h1.state == h0.state == "done"
        assert h1.tokens == h0.tokens
        o1, o0 = np.stack(h1.outputs), np.stack(h0.outputs)
        assert np.allclose(o1, o0, rtol=1e-5, atol=1e-6)


def test_verify_builder_clean_and_bit_exact():
    """build_paged_verify standalone: ptc-verify reports zero findings
    and the fold matches the shared-fold oracle bit-exactly."""
    from parsec_tpu.analysis import verify_taskpool
    from parsec_tpu.ops.paged_attention import (
        PagePool, SeqSpec, attend_page, build_paged_verify,
        finalize_attention, make_slot_collections, reset_acc)
    D, P = 8, 4
    rng = np.random.RandomState(5)
    with pt.Context(nb_workers=2, scheduler="lws") as ctx:
        pool = PagePool(ctx, 10, P, D, name="KV")
        Qc, ACCc, Oc, _, names = make_slot_collections(ctx, 4, D,
                                                       name="PV")
        seqs, want = [], []
        for i, (npg, fill) in enumerate(((3, 2), (1, 4), (2, 1))):
            pages = pool.reserve(npg)
            rows = (npg - 1) * P + fill
            K = rng.randn(rows, D).astype(np.float32)
            V = rng.randn(rows, D).astype(np.float32)
            q = rng.randn(D).astype(np.float32)
            for j, pg in enumerate(pages):
                upto = min(P, rows - j * P)
                pool.k_tile(pg)[:upto] = K[j * P:j * P + upto]
                pool.v_tile(pg)[:upto] = V[j * P:j * P + upto]
            Qc.tile(i, 0)[0] = q
            reset_acc(ACCc.tile(i, 0))
            seqs.append(SeqSpec(i, pages, fill))
            acc = np.zeros(D, np.float32)
            m, l = np.float32(-1.0e30), np.float32(0.0)
            for off in range(0, rows, P):
                acc, m, l = attend_page(q, K[off:off + P], V[off:off + P],
                                        acc, m, l, D ** -0.5)
            want.append(finalize_attention(acc, l))
        tp = build_paged_verify(ctx, pool, seqs, names)
        r = verify_taskpool(tp)
        assert r.ok(), r.text()
        tp.run()
        tp.wait()
        for i in range(3):
            assert np.array_equal(Oc.tile(i, 0)[0], want[i]), i
