"""Durable, migratable KV state pins (ISSUE 11 acceptance criteria).

  (a) Artifact layer (host-only, no device): RequestArtifact /
      PrefixCacheArtifact round-trip byte-exactly through the
      manifest+bin directory format, refuse malformed panels, refuse
      foreign format versions, and `require_tag` fails loudly on a
      param-version mismatch. A crash-shaped directory (payload
      without manifest) reads as ABSENT, never half-loaded.
  (b) BlockPool.adopt: restored blocks join the cached/LRU tier with
      full accounting invariants (check() after every operation), are
      matchable, evictable, and tracked for `prefix_restore_hits`.
  (c) PREEMPTION: at full block occupancy an interactive-class request
      takes a batch-class slot's blocks (brownout preempt verb); the
      preempted stream resumes BIT-IDENTICALLY (== an uninterrupted
      solo run), the pool survives churn with zero leaks, the
      memory-gate scan admits a claimant parked BEHIND a blocked
      lower-class request (the head-of-line inversion regression), and
      the NON-preempting path adds zero device dispatches per token
      (counter A/B). Composes with chunked prefill and speculation.
  (d) MIGRATION: a live request exported from server A and imported
      into server B resumes bit-identical to a solo run on B; the
      local future fails RequestMigratedError; cross-params migration
      refuses loudly (KVStateVersionError).
  (e) PERSISTENT PREFIX CACHE: stop() saves, a restarted server
      warm-starts (prefix_restore_hits > 0) with a stream bit-identical
      to a cold server's, and a snapshot saved under params v1 restored
      into v2 refuses the blocks loudly with ZERO silent reuse.
"""
import os
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
from deeplearning4j_tpu.serving import (BlockPool, BrownoutPolicy,
                                        ContinuousDecodeServer,
                                        KVStateError,
                                        KVStateVersionError, NGramDraft,
                                        PrefixCacheArtifact,
                                        RequestArtifact,
                                        RequestMigratedError, Speculator)
from deeplearning4j_tpu.serving.kvstate import artifact_kind


def _lm(seed=3):
    return TransformerLM(64, d_model=32, n_heads=2, n_layers=2,
                         max_len=64, seed=seed)


def _paged(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("prompt_buckets", (8, 16))
    kw.setdefault("block_size", 4)
    kw.setdefault("n_blocks", 40)
    return ContinuousDecodeServer(lm, paged=True, **kw)


def _wait_tokens(srv, n, timeout=30.0):
    """Block until the server has emitted >= n tokens total."""
    t0 = time.monotonic()
    while srv.metrics.snapshot().get("tokens_out", 0) < n:
        if time.monotonic() - t0 > timeout:
            raise TimeoutError(f"no {n} tokens after {timeout}s")
        time.sleep(0.003)


def _panels(rows=6, layers=2, h=2, hd=16, seed=0):
    r = np.random.default_rng(seed)
    return [(r.standard_normal((rows, h, hd)).astype(np.float32),
             r.standard_normal((rows, h, hd)).astype(np.float32))
            for _ in range(layers)]


# ---------------------------------------------------------------------------
# (a) artifact layer: host-only serialization pins
# ---------------------------------------------------------------------------
class TestArtifacts:
    def test_request_artifact_round_trip_byte_exact(self, tmp_path):
        art = RequestArtifact([1, 2, 3], [9, 8, 7, 6], 10, "tagA", 4,
                              _panels(rows=6), klass="batch")
        p = art.save(str(tmp_path / "req"))
        assert artifact_kind(p) == "request"
        back = RequestArtifact.load(p)
        assert back.prompt == (1, 2, 3)
        assert back.generated == (9, 8, 7, 6)
        assert back.max_new == 10 and back.tag == "tagA"
        assert back.block_size == 4 and back.klass == "batch"
        assert back.pos == 6 and back.remaining == 6
        assert back.nbytes == art.nbytes
        for (k0, v0), (k1, v1) in zip(art.panels, back.panels):
            np.testing.assert_array_equal(k0, k1)
            np.testing.assert_array_equal(v0, v1)

    def test_prefix_artifact_round_trip_parent_first(self, tmp_path):
        # entries handed in child-first are stored parent-first
        e_child = (tuple(range(8)), _panels(rows=4, seed=1))
        e_parent = (tuple(range(4)), _panels(rows=4, seed=2))
        art = PrefixCacheArtifact("tagB", 4, [e_child, e_parent])
        assert [len(p) for p, _ in art.entries] == [4, 8]
        p = art.save(str(tmp_path / "pc"))
        assert artifact_kind(p) == "prefix_cache"
        back = PrefixCacheArtifact.load(p)
        assert [len(pp) for pp, _ in back.entries] == [4, 8]
        np.testing.assert_array_equal(back.entries[0][1][0][0],
                                      e_parent[1][0][0])

    def test_byte_round_trip_and_disk_wire_layout_shared(self, tmp_path):
        """The ISSUE 14 serialization satellite: `to_bytes()` carries
        the manifest+panels layout as ONE buffer (the serving wire's
        MIGRATE payload), round-trips byte-exactly, and its payload
        section is BYTE-IDENTICAL to the on-disk panels.bin — the two
        serializers share `_serialize_arrays`, so they structurally
        cannot drift."""
        import struct

        from deeplearning4j_tpu.serving.kvstate import artifact_from_bytes
        art = RequestArtifact([1, 2, 3], [9, 8, 7, 6], 10, "tagA", 4,
                              _panels(rows=6), klass="batch",
                              trace={"trace_id": "i0-3", "origin": "i0"})
        buf = art.to_bytes()
        back = RequestArtifact.from_bytes(buf)
        assert back.prompt == art.prompt
        assert back.generated == art.generated
        assert back.max_new == art.max_new and back.tag == art.tag
        assert back.block_size == art.block_size
        assert back.klass == "batch" and back.trace == art.trace
        for (k0, v0), (k1, v1) in zip(art.panels, back.panels):
            np.testing.assert_array_equal(k0, k1)
            np.testing.assert_array_equal(v0, v1)
        # payload section == panels.bin, byte for byte
        p = art.save(str(tmp_path / "req"))
        raw = open(os.path.join(p, "panels.bin"), "rb").read()
        (hlen,) = struct.unpack_from("<I", buf, 0)
        assert buf[4 + hlen:] == raw
        # the kind probe dispatches either artifact kind
        assert isinstance(artifact_from_bytes(buf), RequestArtifact)
        pc = PrefixCacheArtifact("tagB", 4,
                                 [(tuple(range(4)), _panels(rows=4))])
        pc2 = PrefixCacheArtifact.from_bytes(pc.to_bytes())
        assert pc2.entries[0][0] == pc.entries[0][0]
        np.testing.assert_array_equal(pc2.entries[0][1][0][0],
                                      pc.entries[0][1][0][0])
        assert isinstance(artifact_from_bytes(pc.to_bytes()),
                          PrefixCacheArtifact)

    def test_byte_layer_refuses_corruption_loudly(self):
        """Truncation, kind mismatch, and format-version drift on the
        ONE-buffer layer fail with the same loud KVStateError family
        the disk loader uses."""
        from deeplearning4j_tpu.serving.kvstate import artifact_from_bytes
        art = RequestArtifact([1], [2], 4, "t", 4, _panels(rows=1))
        buf = art.to_bytes()
        with pytest.raises(KVStateError):
            RequestArtifact.from_bytes(buf[:3])        # no header
        with pytest.raises(KVStateError):
            artifact_from_bytes(buf[:3])     # same guards on dispatch
        with pytest.raises(KVStateError):
            artifact_from_bytes(buf[:12])    # header cut off
        with pytest.raises(KVStateError, match="request"):
            PrefixCacheArtifact.from_bytes(buf)        # wrong kind
        import json
        import struct
        (hlen,) = struct.unpack_from("<I", buf, 0)
        m = json.loads(buf[4:4 + hlen].decode())
        m["format_version"] = 999
        h = json.dumps(m).encode()
        bad = struct.pack("<I", len(h)) + h + buf[4 + hlen:]
        with pytest.raises(KVStateError, match="format_version"):
            RequestArtifact.from_bytes(bad)

    def test_require_tag_fails_loudly(self):
        art = RequestArtifact([1], [2], 4, "v1-fingerprint", 4,
                              _panels(rows=1))
        art.require_tag("v1-fingerprint")     # no raise
        with pytest.raises(KVStateVersionError, match="v2-fingerprint"):
            art.require_tag("v2-fingerprint")

    def test_malformed_panels_and_wrong_kind_refused(self, tmp_path):
        with pytest.raises(KVStateError):
            RequestArtifact([1], [2], 4, "t", 4,
                            _panels(rows=3))      # rows != pos (1)
        with pytest.raises(KVStateError):
            RequestArtifact([1], [], 4, "t", 4, _panels(rows=0))
        with pytest.raises(KVStateError):
            PrefixCacheArtifact("t", 4, [((1, 2, 3), _panels(rows=4))])
        with pytest.raises(KVStateError, match="uniform"):
            # layer 1 shorter than layer 0: must refuse loudly, never
            # zero-fill at install
            RequestArtifact([1, 2], [3], 4, "t", 4,
                            [_panels(rows=2, seed=1)[0],
                             _panels(rows=1, seed=2)[0]])
        p = RequestArtifact([1], [2], 4, "t", 4,
                            _panels(rows=1)).save(str(tmp_path / "a"))
        with pytest.raises(KVStateError, match="request"):
            PrefixCacheArtifact.load(p)

    def test_crash_shaped_directory_reads_as_absent(self, tmp_path):
        d = tmp_path / "half"
        d.mkdir()
        (d / "panels.bin").write_bytes(b"\x00" * 64)   # no manifest
        assert artifact_kind(str(d)) is None
        with pytest.raises(FileNotFoundError):
            RequestArtifact.load(str(d))

    def test_format_version_refused(self, tmp_path):
        import json
        p = RequestArtifact([1], [2], 4, "t", 4,
                            _panels(rows=1)).save(str(tmp_path / "a"))
        m = json.load(open(os.path.join(p, "manifest.json")))
        m["format_version"] = 999
        json.dump(m, open(os.path.join(p, "manifest.json"), "w"))
        with pytest.raises(KVStateError, match="format_version"):
            RequestArtifact.load(p)


# ---------------------------------------------------------------------------
# (b) BlockPool.adopt: restored blocks, full invariants
# ---------------------------------------------------------------------------
class TestPoolAdopt:
    def test_adopt_indexes_and_lru_evicts(self):
        pool = BlockPool(4, 4)
        b0 = pool.adopt((0, tuple(range(4))))
        b1 = pool.adopt((0, tuple(range(8))))
        assert b0 is not None and b1 is not None
        assert pool.restored == {b0, b1}
        pool.check()
        assert pool.match_prefix(list(range(8)), tag=0)[1] == 8
        assert pool.adopt((0, tuple(range(4)))) is None   # already there
        # a full pool evicts adopted blocks LRU like any cached block
        a = pool.admit(list(range(20, 36)), 16)
        assert a is not None
        pool.check()
        assert pool.restored == set()     # both evicted and unmarked
        pool.release(a)
        pool.check()

    def test_adopted_block_shared_by_admission(self):
        pool = BlockPool(8, 4)
        b0 = pool.adopt((0, tuple(range(1, 5))))
        a = pool.admit(list(range(1, 9)), 10, tag=0)
        assert a.shared_rows == 4 and a.ids[0] == b0
        pool.check()
        pool.release(a)
        pool.check()


# ---------------------------------------------------------------------------
# (c) preemption
# ---------------------------------------------------------------------------
class TestPreemption:
    def _brownout(self):
        return BrownoutPolicy(classes={"batch": (0.9, 1.01)})

    def test_preempt_verb_ranking(self):
        pol = self._brownout()
        assert pol.may_preempt("batch", "interactive")
        assert pol.may_preempt("batch", "default")
        assert not pol.may_preempt("interactive", "batch")
        assert not pol.may_preempt("batch", "batch")
        assert not pol.may_preempt("default", "default")

    def test_preempt_requires_paged_and_brownout(self):
        lm = _lm()
        with pytest.raises(ValueError, match="paged"):
            ContinuousDecodeServer(lm, preempt=True,
                                   brownout=self._brownout())
        with pytest.raises(ValueError, match="brownout"):
            _paged(lm, preempt=True)

    def test_preempted_stream_bit_identical_and_pool_clean(self):
        lm = _lm()
        srv = _paged(lm, slots=2, prompt_buckets=(8,), n_blocks=10,
                     brownout=self._brownout(), preempt=True).start()
        try:
            # batch reserves 8 of 10 blocks; after the second
            # interactive (3 blocks) only preemption can admit it
            bfut = srv.submit([1, 2, 3, 4, 5, 6], 26, klass="batch")
            _wait_tokens(srv, 2)
            i1 = srv.submit([7, 8, 9], 6, klass="interactive")
            i2 = srv.submit([9, 8, 7, 6], 8, klass="interactive")
            r2, r1, rb = i2.result(120), i1.result(120), bfut.result(240)
            snap = srv.metrics.snapshot()
            assert snap["preempted"] >= 1
            assert snap["resumed"] >= 1
            assert snap["spill_bytes"] > 0
        finally:
            srv.stop(timeout=120)
        srv._pool.check()
        assert srv._pool.blocks_in_use == 0
        with _paged(lm, slots=2, prompt_buckets=(8,)) as solo:
            assert rb == solo.generate([1, 2, 3, 4, 5, 6], 26,
                                       timeout=120)
            assert r1 == solo.generate([7, 8, 9], 6, timeout=120)
            assert r2 == solo.generate([9, 8, 7, 6], 8, timeout=120)

    def test_claimant_behind_blocked_batch_still_preempts(self):
        """Head-of-line inversion regression: a second BATCH request
        parks blocked on the memory gate; an interactive request
        arriving behind it must still reach its preemption chance (the
        preempting gate scans past blocked requests instead of walling
        the line)."""
        lm = _lm()
        srv = _paged(lm, slots=3, prompt_buckets=(8,), n_blocks=8,
                     brownout=self._brownout(), preempt=True).start()
        try:
            b1 = srv.submit([1, 2, 3, 4], 28, klass="batch")  # 8 blocks
            _wait_tokens(srv, 2)
            b2 = srv.submit([4, 3, 2, 1], 28, klass="batch")  # blocked
            # straight behind it: b1 has 26 tokens left, which a fast host
            # decodes within a pause, and then nothing needs preempting
            i1 = srv.submit([5, 6, 7], 5, klass="interactive")
            r1 = i1.result(60)      # would hang without the gate scan
            snap = srv.metrics.snapshot()
            assert snap["preempted"] >= 1
            rb1, rb2 = b1.result(240), b2.result(240)
        finally:
            srv.stop(timeout=120)
        srv._pool.check()
        assert srv._pool.blocks_in_use == 0
        with _paged(lm, slots=2, prompt_buckets=(8,)) as solo:
            assert rb1 == solo.generate([1, 2, 3, 4], 28, timeout=120)
            assert rb2 == solo.generate([4, 3, 2, 1], 28, timeout=120)
            assert r1 == solo.generate([5, 6, 7], 5, timeout=120)

    def test_composes_with_chunked_prefill_and_speculation(self):
        lm = _lm()
        spec = Speculator(NGramDraft(n=3), k=4)
        srv = _paged(lm, slots=2, prompt_buckets=(16,), n_blocks=12,
                     chunked_prefill=4, speculate=spec,
                     brownout=self._brownout(), preempt=True).start()
        try:
            bfut = srv.submit([1, 2, 3, 1, 2, 3, 1, 2], 32,
                              klass="batch")   # 39 rows -> 10 blocks
            _wait_tokens(srv, 2)
            ifut = srv.submit([5, 6, 5, 6, 5], 8, klass="interactive")
            ri, rb = ifut.result(120), bfut.result(240)
            snap = srv.metrics.snapshot()
            assert snap["preempted"] >= 1 and snap["resumed"] >= 1
        finally:
            srv.stop(timeout=120)
        srv._pool.check()
        assert srv._pool.blocks_in_use == 0
        # spec + chunked preempted streams == plain greedy solo
        with _paged(lm, slots=2, prompt_buckets=(16,)) as solo:
            assert rb == solo.generate([1, 2, 3, 1, 2, 3, 1, 2], 32,
                                       timeout=120)
            assert ri == solo.generate([5, 6, 5, 6, 5], 8, timeout=120)

    def test_property_churn_admit_preempt_resume_release(self):
        """Satellite pin: random interleaving of admissions (both
        classes), preemptions (forced by interactive pressure),
        resumes, and releases — the pool's invariants hold at drain
        with ZERO leaked blocks and an empty pool."""
        lm = _lm()
        rng = np.random.default_rng(7)
        srv = _paged(lm, slots=3, prompt_buckets=(8,), n_blocks=14,
                     brownout=self._brownout(), preempt=True).start()
        futs = []
        try:
            for i in range(40):
                if rng.random() < 0.35:
                    p = rng.integers(1, 60, 4).tolist()
                    futs.append(srv.submit(p, int(rng.integers(16, 30)),
                                           klass="batch"))
                else:
                    p = rng.integers(1, 60, int(rng.integers(2, 6)))
                    futs.append(srv.submit(p.tolist(),
                                           int(rng.integers(2, 9)),
                                           klass="interactive"))
                if rng.random() < 0.3:
                    time.sleep(0.004)
            for f in futs:
                f.result(300)
            snap = srv.metrics.snapshot()
        finally:
            srv.stop(timeout=180)
        srv._pool.check()
        assert srv._pool.blocks_in_use == 0
        assert srv._pool.blocks_free == srv._pool.capacity
        assert snap["completed"] == len(futs)

    def test_preempted_request_survives_hot_swap(self):
        """A request preempted BEFORE a hot swap resumes under the
        params it started with (its version is pinned while parked —
        the artifact's rows are only valid there), bit-identical to a
        solo run on the OLD params, while post-swap requests get the
        new params."""
        lm, lm2 = _lm(seed=3), _lm(seed=11)
        srv = _paged(lm, slots=2, prompt_buckets=(8,), n_blocks=10,
                     brownout=self._brownout(), preempt=True).start()
        try:
            b = srv.submit([1, 2, 3, 4, 5, 6], 26, klass="batch")
            _wait_tokens(srv, 2)
            i = srv.submit([9, 8, 7, 6], 8, klass="interactive")
            i.result(120)
            assert srv.metrics.snapshot()["preempted"] >= 1
            srv.swap(lm2)
            post = srv.submit([7, 7, 7], 5)
            rb, rp = b.result(240), post.result(120)
        finally:
            srv.stop(timeout=120)
        srv._pool.check()
        with _paged(lm, slots=2, prompt_buckets=(8,)) as solo_old:
            assert rb == solo_old.generate([1, 2, 3, 4, 5, 6], 26,
                                           timeout=120)
        with _paged(lm2, slots=2, prompt_buckets=(8,)) as solo_new:
            assert rp == solo_new.generate([7, 7, 7], 5, timeout=120)

    def test_non_preempting_path_zero_added_dispatches(self):
        """Dispatch-counter A/B (acceptance pin): with preemption
        ENABLED but never triggered (ample blocks), the dispatch count
        for an identical workload equals the preempt=False server's —
        durable KV state costs zero device dispatches per token until
        a spill actually happens."""
        lm = _lm()
        work = [([1, 2, 3, 4], 6), ([5, 6, 7], 9), ([8, 9], 5)]
        counts = {}
        for name, kw in (("preempt_on",
                          dict(brownout=self._brownout(), preempt=True)),
                         ("preempt_off", {})):
            srv = _paged(lm, slots=4, n_blocks=40, **kw).start()
            try:
                srv.generate([1, 2], 2, timeout=120)    # warm compile
                base = srv.metrics.snapshot()["dispatches"]
                futs = [srv.submit(p, n, klass="interactive")
                        for p, n in work]
                for f in futs:
                    f.result(120)
                snap = srv.metrics.snapshot()
                counts[name] = snap["dispatches"] - base
                assert snap["preempted"] == 0
            finally:
                srv.stop(timeout=120)
            srv._pool.check()
        assert counts["preempt_on"] == counts["preempt_off"]

    def test_preempted_request_deadline_enforced(self):
        """A preempted request's deadline stays enforced: whether it
        expires while PARKED on the resume line (the resume-line sweep)
        or right after resuming (mid-decode eviction), the future fails
        loudly with DeadlineExceededError and every block is back in
        the pool. The interactive claimant reserves the WHOLE pool, so
        the batch request is guaranteed parked for the interactive's
        full runtime — far past its budget on any machine."""
        from deeplearning4j_tpu.serving import DeadlineExceededError
        lm = _lm()
        srv = _paged(lm, slots=2, prompt_buckets=(8,), n_blocks=13,
                     brownout=self._brownout(), preempt=True).start()
        try:
            srv.generate([9, 9], 2, timeout=120)    # compile off clock
            b = srv.submit([1, 2, 3, 4, 5, 6], 26, klass="batch",
                           deadline_ms=60.0)
            _wait_tokens(srv, 2)
            # whole-pool interactive: 4 + 49 - 1 = 52 rows = 13 blocks
            i = srv.submit([7, 8, 9, 1], 49, klass="interactive")
            i.result(120)
            with pytest.raises(DeadlineExceededError):
                b.result(120)
            snap = srv.metrics.snapshot()
            assert snap["preempted"] >= 1
        finally:
            srv.stop(timeout=120)
        srv._pool.check()
        assert srv._pool.blocks_in_use == 0


# ---------------------------------------------------------------------------
# (d) migration
# ---------------------------------------------------------------------------
class TestMigration:
    def test_migrated_stream_bit_identical_to_solo(self):
        lm = _lm()
        a = _paged(lm).start()
        b = _paged(lm).start()
        try:
            with _paged(lm) as solo:
                ref = solo.generate([5, 9, 2, 7, 1, 3], 20, timeout=120)
            fut = a.submit([5, 9, 2, 7, 1, 3], 20)
            _wait_tokens(a, 4)
            art = a.migrate_out(fut)
            assert len(art.generated) >= 1
            with pytest.raises(RequestMigratedError):
                fut.result(10)
            out = b.migrate_in(art).result(120)
            assert out == ref
            assert b.metrics.snapshot()["migrated"] == 1
            assert a.metrics.snapshot()["spill_bytes"] > 0
        finally:
            a.stop(timeout=120)
            b.stop(timeout=120)
        a._pool.check()
        b._pool.check()
        assert a._pool.blocks_in_use == 0
        assert b._pool.blocks_in_use == 0

    def test_migration_composes_with_speculation_and_chunking(self):
        lm = _lm()
        kw = dict(slots=2, prompt_buckets=(16,), chunked_prefill=4,
                  speculate=Speculator(NGramDraft(n=3), k=4))
        a = _paged(lm, **kw).start()
        b = _paged(lm, **kw).start()
        try:
            prompt = [1, 2, 3, 1, 2, 3, 1, 2, 3]
            with _paged(lm, slots=2, prompt_buckets=(16,)) as solo:
                ref = solo.generate(prompt, 24, timeout=120)
            fut = a.submit(prompt, 24)
            _wait_tokens(a, 4)
            art = a.migrate_out(fut)
            out = b.migrate_in(art).result(120)
            assert out == ref
        finally:
            a.stop(timeout=120)
            b.stop(timeout=120)
        a._pool.check()
        b._pool.check()

    def test_cross_params_migration_refused_loudly(self):
        a = _paged(_lm(seed=3)).start()
        b = _paged(_lm(seed=9)).start()
        try:
            fut = a.submit([5, 9, 2, 7], 12)
            _wait_tokens(a, 2)
            art = a.migrate_out(fut)
            with pytest.raises(KVStateVersionError):
                b.migrate_in(art)
            assert b.metrics.snapshot()["migrated"] == 0
        finally:
            a.stop(timeout=120)
            b.stop(timeout=120)
        b._pool.check()
        assert b._pool.blocks_in_use == 0

    def test_unknown_request_export_fails_loudly(self):
        import concurrent.futures as cf
        srv = _paged(_lm()).start()
        try:
            with pytest.raises(KVStateError, match="not found"):
                srv.migrate_out(cf.Future())
        finally:
            srv.stop(timeout=120)

    def test_restore_onto_partial_block_ride_never_corrupts_owner(self):
        """Regression: a restored request whose prefix match rides the
        FIRST PART of a shared partial block must not install its rows
        into that block — rows [shared, pos) would overwrite the cached
        owner's tail (E,F,G,H of a block another prompt still matches).
        The restore materializes the reserved CoW spare FIRST and
        installs the whole block from the artifact. Detector: a later
        full-prefix hit on the owner's prompt must still be
        bit-identical to a cold run."""
        lm = _lm()
        P8 = [1, 2, 3, 4, 5, 6, 7, 8]       # 2 full blocks at bs=4
        P6 = P8[:6]                         # full block + 2-row partial
        with _paged(lm, slots=2, prompt_buckets=(8,)) as solo:
            ref8 = solo.generate(P8, 6, timeout=120)
            ref6 = solo.generate(P6, 24, timeout=120)
        srv = _paged(lm, slots=2, prompt_buckets=(8,)).start()
        try:
            assert srv.generate(P8, 6, timeout=120) == ref8   # indexed
            f2 = srv.submit(P6, 24)         # partial ride + CoW
            # wait on the SLOT STATE, not the shared token counter: a
            # counter threshold can be crossed arbitrarily close to
            # the request's own completion on a slow box, and a
            # completed request is (correctly) no longer exportable —
            # observed as a rare machine-weather flake. Decode-phase
            # occupancy plus a 24-token budget leaves ~20 tokens of
            # runway for the export command to land.
            t0 = time.monotonic()
            while not any(r is not None and r.future is f2
                          and r.pf_next is None
                          for r in srv._slot_req):
                assert time.monotonic() - t0 < 60, "never reached decode"
                time.sleep(0.002)
            art = srv.migrate_out(f2)
            out2 = srv.migrate_in(art).result(120)
            assert out2 == ref6
            # the owner's blocks must be intact: full-prefix re-hit
            assert srv.generate(P8, 6, timeout=120) == ref8
        finally:
            srv.stop(timeout=120)
        srv._pool.check()
        assert srv._pool.blocks_in_use == 0

    def test_artifact_survives_disk_round_trip(self, tmp_path):
        """The migration seam IS the serialization seam: an artifact
        saved to disk and re-loaded imports identically (the
        prefill/decode-disaggregation wire path)."""
        lm = _lm()
        a = _paged(lm).start()
        b = _paged(lm).start()
        try:
            with _paged(lm) as solo:
                ref = solo.generate([3, 1, 4, 1, 5], 16, timeout=120)
            fut = a.submit([3, 1, 4, 1, 5], 16)
            _wait_tokens(a, 3)
            art = a.migrate_out(fut)
            p = art.save(str(tmp_path / "wire"))
            out = b.migrate_in(RequestArtifact.load(p)).result(120)
            assert out == ref
        finally:
            a.stop(timeout=120)
            b.stop(timeout=120)
        a._pool.check()
        b._pool.check()


# ---------------------------------------------------------------------------
# (e) persistent prefix cache
# ---------------------------------------------------------------------------
class TestPersistentPrefixCache:
    SYS = list(range(1, 13))    # 3 full blocks at block_size 4

    def test_restart_warm_start_bit_identical(self, tmp_path):
        lm = _lm()
        pdir = str(tmp_path / "prefix")
        s1 = _paged(lm, slots=2, prompt_buckets=(16,),
                    prefix_cache_dir=pdir).start()
        cold = s1.generate(self.SYS + [20, 21], 8, timeout=120)
        s1.stop(timeout=120)
        assert artifact_kind(pdir) == "prefix_cache"
        s2 = _paged(lm, slots=2, prompt_buckets=(16,),
                    prefix_cache_dir=pdir).start()
        try:
            s2._pool.check()
            warm = s2.generate(self.SYS + [20, 21], 8, timeout=120)
            snap = s2.metrics.snapshot()
        finally:
            s2.stop(timeout=120)
        assert warm == cold
        assert snap["prefix_restore_hits"] > 0
        s2._pool.check()
        assert s2._pool.blocks_in_use == 0

    def test_version_mismatch_refused_loudly_zero_reuse(self, tmp_path):
        """Satellite pin: a snapshot saved under params v1 restored
        into a server running v2 refuses the blocks loudly — the
        constructor raises, and a direct restore attempt adopts ZERO
        blocks (the in-process hot-swap invalidation rule, across
        restarts)."""
        pdir = str(tmp_path / "prefix")
        s1 = _paged(_lm(seed=3), slots=2, prompt_buckets=(16,),
                    prefix_cache_dir=pdir).start()
        s1.generate(self.SYS + [20, 21], 8, timeout=120)
        s1.stop(timeout=120)
        with pytest.raises(KVStateVersionError):
            _paged(_lm(seed=9), slots=2, prompt_buckets=(16,),
                   prefix_cache_dir=pdir)
        # direct restore into a v2 server without the dir wiring: same
        # loud refusal, zero adopted blocks
        s2 = _paged(_lm(seed=9), slots=2, prompt_buckets=(16,))
        with pytest.raises(KVStateVersionError):
            s2.restore_prefix_cache(pdir)
        assert s2._pool.restored == set()
        assert s2._pool.blocks_free == s2._pool.capacity
        s2._pool.check()

    def test_small_pool_restores_prefix_of_snapshot(self, tmp_path):
        """A pool smaller than the snapshot adopts what fits (parent-
        first, so what it adopts is matchable) and never fails the
        server."""
        lm = _lm()
        pdir = str(tmp_path / "prefix")
        s1 = _paged(lm, slots=2, prompt_buckets=(16,), n_blocks=40,
                    prefix_cache_dir=pdir).start()
        s1.generate(self.SYS + [20, 21], 8, timeout=120)
        s1.generate(list(range(30, 42)) + [1], 8, timeout=120)
        s1.stop(timeout=120)
        art = PrefixCacheArtifact.load(pdir)
        assert len(art.entries) >= 4
        s2 = _paged(lm, slots=1, prompt_buckets=(16,), n_blocks=3,
                    max_blocks_per_slot=16)
        n = s2.restore_prefix_cache(pdir)
        assert 0 < n <= 3
        s2._pool.check()

    def test_stale_snapshot_removed_when_nothing_saveable(self, tmp_path):
        """Regression: a server that hot-swaps and then stops with no
        prefix entries under the NEWEST version must not leave the
        previous version's snapshot behind — a stale artifact would
        strand the next constructor on a version refusal the server's
        own lifecycle caused. The save removes it; the next start is a
        clean cold start."""
        lm, lm2 = _lm(seed=3), _lm(seed=11)
        pdir = str(tmp_path / "prefix")
        s1 = _paged(lm, slots=2, prompt_buckets=(16,),
                    prefix_cache_dir=pdir).start()
        s1.generate(self.SYS + [20, 21], 8, timeout=120)
        s1.stop(timeout=120)
        assert artifact_kind(pdir) == "prefix_cache"
        s2 = _paged(lm, slots=2, prompt_buckets=(16,),
                    prefix_cache_dir=pdir).start()     # warm restore OK
        s2.swap(lm2)            # newest version now has no entries
        s2.stop(timeout=120)    # save finds nothing: stale dir removed
        assert artifact_kind(pdir) is None
        # the new-params server boots cold instead of raising
        s3 = _paged(lm2, slots=2, prompt_buckets=(16,),
                    prefix_cache_dir=pdir).start()
        try:
            s3.generate(self.SYS + [20, 21], 8, timeout=120)
        finally:
            s3.stop(timeout=120)
        assert artifact_kind(pdir) == "prefix_cache"

    def test_explicit_foreign_path_never_deleted(self, tmp_path):
        """save_prefix_cache with nothing saveable removes only the
        server's OWN stale prefix_cache_dir; an explicitly passed path
        may be another server's valid snapshot and must survive."""
        lm = _lm()
        pdir = str(tmp_path / "prefix")
        s1 = _paged(lm, slots=2, prompt_buckets=(16,),
                    prefix_cache_dir=pdir).start()
        s1.generate(self.SYS + [20, 21], 8, timeout=120)
        s1.stop(timeout=120)
        assert artifact_kind(pdir) == "prefix_cache"
        s2 = _paged(lm)             # never started: nothing saveable
        assert s2.save_prefix_cache(pdir) is None
        assert artifact_kind(pdir) == "prefix_cache"    # intact

    def test_save_without_dir_and_on_running_server_refused(self):
        srv = _paged(_lm()).start()
        try:
            with pytest.raises(KVStateError, match="stopped"):
                srv.save_prefix_cache("/tmp/nope")
        finally:
            srv.stop(timeout=120)
        with pytest.raises(ValueError, match="path"):
            srv.save_prefix_cache()

    def test_prefix_dir_requires_paged_prefix_cache(self, tmp_path):
        with pytest.raises(ValueError, match="prefix_cache_dir"):
            ContinuousDecodeServer(_lm(),
                                   prefix_cache_dir=str(tmp_path / "x"))


# ---------------------------------------------------------------------------
# snapshot keys
# ---------------------------------------------------------------------------
class TestDurableMetricsKeys:
    def test_keys_always_present_and_zero_when_idle(self):
        from deeplearning4j_tpu.serving import ServingMetrics
        snap = ServingMetrics().snapshot()
        for key in ("preempted", "resumed", "migrated", "migrated_out",
                    "spill_bytes", "prefix_restore_hits"):
            assert snap[key] == 0
