"""Versioned fleet weight push (ISSUE 14): codec/manifest units, loopback
publish→fetch bit-exactness + counters, the relay chain (root ships each
chunk once), and the serving/elastic consumers."""

import threading

import numpy as np
import pytest

from uccl_tpu import obs
from uccl_tpu.p2p import Channel, Endpoint, WeightPublisher
from uccl_tpu.p2p import weight_push as wp


def chan_pair(server_ep, client_ep, n_paths=2):
    res = {}
    t = threading.Thread(
        target=lambda: res.setdefault("c", Channel.accept(server_ep)))
    t.start()
    c = Channel.connect(client_ep, "127.0.0.1", server_ep.port,
                        n_paths=n_paths)
    t.join(timeout=20)
    assert "c" in res, "channel accept timed out"
    return res["c"], c


def small_tree(rng, scale=1):
    return {
        "layers": [
            {"w": rng.standard_normal((32 * scale, 16)).astype(np.float32),
             "b": rng.standard_normal(16).astype(np.float32)}
            for _ in range(2)
        ],
        "emb": rng.standard_normal((64, 8)).astype(np.float32),
        "step": np.asarray([42], np.int64),
    }


def trees_equal(a, b):
    fa = {k: v for k, v in wp.flatten_tree(a)}
    fb = {k: v for k, v in wp.flatten_tree(b)}
    return (set(fa) == set(fb)
            and all(np.array_equal(fa[k], fb[k]) for k in fa))


class TestTreeCodec:
    def test_flatten_unflatten_roundtrip(self, rng):
        tree = small_tree(rng)
        pairs = wp.flatten_tree(tree)
        assert [k for k, _ in pairs] == sorted(k for k, _ in pairs)
        rebuilt = wp.unflatten_tree(dict(pairs))
        assert isinstance(rebuilt["layers"], list)
        assert trees_equal(tree, rebuilt)

    def test_empty_tree_rejected(self):
        with pytest.raises(ValueError):
            wp.flatten_tree({})
        with pytest.raises(ValueError):
            wp.flatten_tree({"a": {}})

    def test_manifest_groups_cover_everything(self, rng):
        pub = WeightPublisher(group_bytes=1024)
        pub.publish("m", small_tree(rng))
        snap = pub.get("m")
        ents = snap.manifest["entries"]
        covered = []
        for g, (lo, hi) in enumerate(snap.manifest["groups"]):
            covered.extend(range(lo, hi))
            a, b = snap.group_range(g)
            assert b > a
            assert snap.group_crc(g) == snap.manifest["group_crcs"][g]
        assert covered == list(range(len(ents)))
        total = sum(int(e["nbytes"]) for e in ents)
        assert total == snap.total_bytes == snap.buf.nbytes

    def test_versioning_and_eviction(self, rng):
        pub = WeightPublisher(keep_versions=2)
        t = small_tree(rng)
        assert pub.publish("m", t) == 1
        assert pub.publish("m", t) == 2
        assert pub.publish("m", t) == 3
        assert pub.get("m").version == 3
        assert pub.get("m", 2).version == 2
        with pytest.raises(KeyError):  # evicted by keep_versions=2
            pub.get("m", 1)
        with pytest.raises(KeyError):
            pub.get("nope")
        with pytest.raises(ValueError):
            pub.publish("m", t, version=3)  # already published

    def test_fp8_wire_canonicalized_once(self, rng):
        """The published fp8 version is its own canonical bytes: decode
        is deterministic and within the codec's round trip of the
        input; non-float leaves ship raw (bit-exact)."""
        pub = WeightPublisher()
        tree = small_tree(rng)
        pub.publish("m", tree, wire="fp8")
        flat = pub.get("m").flat()
        assert np.array_equal(flat["step"], tree["step"])  # raw non-float
        w = tree["layers"][0]["w"]
        got = flat["layers.0.w"]
        assert not np.array_equal(got, w)  # lossy...
        np.testing.assert_allclose(got, w, rtol=0.2, atol=0.1)  # ...bounded
        with pytest.raises(ValueError):
            pub.publish("m2", tree, wire="nope")


class TestLoopback:
    def test_publish_fetch_bit_exact_with_counters(self, rng):
        pub = WeightPublisher(group_bytes=8 << 10)
        tree = small_tree(rng)
        v = pub.publish("model", tree)
        rx0 = obs.counter("weight_push_bytes_total").get(role="rx",
                                                         name="model")
        peers0 = obs.counter("weight_push_peers_total").get(name="model")
        verb0 = obs.counter("p2p_bytes_total").get(verb="weight_push")
        with Endpoint(n_engines=2) as pep, Endpoint(n_engines=2) as sep:
            srv, cli = chan_pair(pep, sep)
            t = threading.Thread(target=lambda: pub.serve(srv))
            t.start()
            snap = wp.fetch(cli, "model")
            t.join(timeout=20)
        assert snap.version == v
        assert trees_equal(snap.tree(), tree)
        total = snap.total_bytes
        assert obs.counter("weight_push_bytes_total").get(
            role="rx", name="model") == rx0 + total
        assert obs.counter("weight_push_peers_total").get(
            name="model") == peers0 + 1
        assert obs.counter("p2p_bytes_total").get(
            verb="weight_push") >= verb0 + total

    def test_fetch_unknown_name_fails_loudly(self, rng):
        pub = WeightPublisher()
        pub.publish("model", small_tree(rng))
        with Endpoint(n_engines=2) as pep, Endpoint(n_engines=2) as sep:
            srv, cli = chan_pair(pep, sep)
            err = []

            def serve():
                try:
                    pub.serve(srv)
                except KeyError as e:
                    err.append(e)

            t = threading.Thread(target=serve)
            t.start()
            with pytest.raises(Exception):
                wp.fetch(cli, "other", timeout_ms=3000)
            t.join(timeout=20)
            assert err  # server named the missing snapshot


@pytest.mark.slow
class TestRelayChain:
    @pytest.mark.parametrize("wire", [None, "fp8"])
    def test_three_peer_chain_bit_exact(self, rng, wire):
        """root -> s1 -> s2 -> s3: every peer bit-exact vs the PUBLISHED
        version, and the root's counted egress stays ONE snapshot (the
        peers forwarded the rest)."""
        pub = WeightPublisher(group_bytes=16 << 10)
        tree = small_tree(rng, scale=4)
        pub.publish("m", tree, wire=wire)
        canon = pub.get("m").flat()
        fam = obs.counter("weight_push_bytes_total")
        root0 = fam.get(role="tx", name="m", src="publisher")
        eps = [Endpoint(n_engines=2) for _ in range(4)]
        try:
            d0, u1 = chan_pair(eps[0], eps[1])
            d1, u2 = chan_pair(eps[1], eps[2])
            d2, u3 = chan_pair(eps[2], eps[3])
            snaps = {}

            def node(i, up, downs):
                snaps[i] = wp.fetch(up, "m", forward_to=downs)

            ts = [threading.Thread(target=node, args=(1, u1, [d1])),
                  threading.Thread(target=node, args=(2, u2, [d2])),
                  threading.Thread(target=node, args=(3, u3, []))]
            for t in ts:
                t.start()
            pub.serve(d0)
            for t in ts:
                t.join(timeout=60)
            assert sorted(snaps) == [1, 2, 3]
            for i in (1, 2, 3):
                flat = snaps[i].flat()
                assert all(np.array_equal(flat[k], canon[k])
                           for k in canon), f"peer {i} diverged"
            snap = pub.get("m")
            assert fam.get(role="tx", name="m", src="publisher") \
                == root0 + snap.total_bytes
        finally:
            for ep in eps:
                ep.close()


class TestConsumers:
    def test_replicate_backend_serves_pushed_version(self, rng):
        """replicate_backend(weights=) spins every replica up on the
        fetched tree (structure-validated), sharing the prototype's
        compiled-fn cache."""
        import jax

        from uccl_tpu.models import dense
        from uccl_tpu.serving.engine import DenseBackend, replicate_backend

        cfg = dense.DenseConfig(vocab=32, dim=16, n_layers=1, n_heads=2,
                                n_kv_heads=1, head_dim=8, ffn=32)
        params = dense.init_params(jax.random.PRNGKey(0), cfg)
        backend = DenseBackend(params, cfg, n_slots=2, max_seq=16)
        pub = WeightPublisher()
        pub.publish("dense", jax.tree_util.tree_map(np.asarray, params))
        reps = replicate_backend(backend, 2, weights=pub.get("dense"))
        assert len(reps) == 2
        assert reps[0].programs is reps[1].programs is backend.programs
        for a, b in zip(jax.tree_util.tree_leaves(reps[1].params),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # structure mismatches fail loudly before any replica serves
        bad = {"not": np.zeros(3, np.float32)}
        with pytest.raises(ValueError):
            replicate_backend(backend, 2, weights=bad)

    def test_warm_spare_admit_counts_weight_push_bytes(self, rng):
        """ep/elastic warm-spare admission: a snapshot import rides the
        fetch's counted bytes; a raw-tree import (the legacy untracked
        copy) is counted HERE on p2p_bytes_total{verb=weight_push}."""
        from uccl_tpu.ep.elastic import ElasticBuffer, admit_warm_spare

        tree = {"w": rng.standard_normal((16, 16)).astype(np.float32)}
        buf = ElasticBuffer(1 << 20)
        before = obs.counter("p2p_bytes_total").get(verb="weight_push")
        n = admit_warm_spare(buf, tree)
        assert n == 16 * 16 * 4
        assert obs.counter("p2p_bytes_total").get(
            verb="weight_push") == before + n
        assert buf.names() == ["w"]
        pub = WeightPublisher()
        pub.publish("m", tree)
        before = obs.counter("p2p_bytes_total").get(verb="weight_push")
        admit_warm_spare(buf, pub.get("m"), prefix="v1.")
        # snapshot bytes were counted at fetch time, not re-counted here
        assert obs.counter("p2p_bytes_total").get(
            verb="weight_push") == before
        np.testing.assert_array_equal(
            np.asarray(buf.get("v1.w")), tree["w"])


class TestResume:
    def test_fault_injected_fetch_resumes_by_group_crc(self, rng):
        """A fetch killed mid-transfer by native data-plane loss resumes
        off its FetchError.partial: already-verified groups are skipped
        (CRC-guarded, counted on weight_push_resumed_groups_total) and
        the completed snapshot is bit-exact vs the published version."""
        pub = WeightPublisher(group_bytes=1024)
        tree = small_tree(rng)
        pub.publish("model", tree)
        n_groups = len(pub.get("model").manifest["groups"])
        assert n_groups >= 3, "resume needs several groups to matter"
        with Endpoint(n_engines=2) as pep, Endpoint(n_engines=2) as sep:
            srv, cli = chan_pair(pep, sep)
            srv.retries = 0  # the serve side gives up fast under loss

            def serve_once():
                try:
                    pub.serve(srv, timeout_ms=4000)
                except Exception:
                    pass  # the faulted serve dies; counted server-side

            t = threading.Thread(target=serve_once)
            t.start()

            # after group 0 verifies, total data-plane loss: the serve
            # side's windowed writev exhausts its attempts and dies, our
            # fetch times out — deterministically partial
            def on_group(g):
                if g == 0:
                    pep.set_drop_rate(1.0)

            with pytest.raises(wp.FetchError) as ei:
                wp.fetch(cli, "model", timeout_ms=1500,
                         on_group=on_group)
            t.join(timeout=30)
            pep.set_drop_rate(0.0)
            err = ei.value
            assert err.partial is not None
            assert 1 <= len(err.groups_ok) < n_groups
            assert err.groups_ok[0] == 0

            # retry with resume: only the missing groups cross the wire
            res0 = obs.counter("weight_push_resumed_groups_total").get()
            rx0 = obs.counter("weight_push_bytes_total").get(
                role="rx", name="model")
            srv2, cli2 = chan_pair(pep, sep)
            t2 = threading.Thread(target=lambda: pub.serve(srv2))
            t2.start()
            snap = wp.fetch(cli2, "model", resume=err.partial)
            t2.join(timeout=30)
            assert trees_equal(snap.tree(), tree)
            skipped = obs.counter(
                "weight_push_resumed_groups_total").get() - res0
            assert skipped == len(err.groups_ok)
            # rx bytes on the resumed fetch exclude the skipped groups
            rx = obs.counter("weight_push_bytes_total").get(
                role="rx", name="model") - rx0
            skipped_bytes = sum(
                snap.group_range(g)[1] - snap.group_range(g)[0]
                for g in err.groups_ok
            )
            assert rx == snap.total_bytes - skipped_bytes

    def test_resume_against_different_version_falls_back_full(self, rng):
        """A stale partial (the publisher moved on) matches nothing: the
        fetch silently degrades to a full transfer, still bit-exact."""
        pub = WeightPublisher(group_bytes=1024)
        tree = small_tree(rng)
        pub.publish("model", tree)
        stale_man = dict(pub.get("model").manifest, version=99)
        stale = wp.WeightSnapshot(stale_man,
                                  pub.get("model").buf.copy())
        res0 = obs.counter("weight_push_resumed_groups_total").get()
        with Endpoint(n_engines=2) as pep, Endpoint(n_engines=2) as sep:
            srv, cli = chan_pair(pep, sep)
            t = threading.Thread(target=lambda: pub.serve(srv))
            t.start()
            snap = wp.fetch(cli, "model", resume=stale)
            t.join(timeout=20)
        assert trees_equal(snap.tree(), tree)
        assert obs.counter(
            "weight_push_resumed_groups_total").get() == res0
