"""Wire codecs for the byte-heavy serving paths (serve/wire.py, PR
"quantized wire exchange").

Unit layer: pure-codec properties with no HTTP and no engine — varint /
ordered-u32 primitives, the q16 candidate codec's ONE load-bearing
invariant (``hi >= d2 >= lo`` per slot, anchor / pad / zero slots exact),
its encode-refusal preconditions (the codec returns None instead of
guessing), d16 slab losslessness down to the bit, chunk framing torn-EOF
detection, and the negotiation table (mismatch = fallback, never error).

Integration layer: one small in-process slab host booted twice — once
``wire="auto"``, once ``wire="f32"`` (the old-binary emulation) — probed
at the raw HTTP level. The acceptance bars from the issue: a q16 request
to an f32-only host falls back to plain f32 (never a decode error), the
x32 survivor re-fetch carries the exact d2 bytes, a no-``?wire=`` request
gets the pre-codec body byte-for-byte, and ``pull_slab_rows`` is lossless
across legacy / chunked-f32 / d16 paths. Plus the drift-aware
``stream_skip_cold`` admission on an injectable clock (TUNING.md's PR-16
caveat): a pool already stalling past ``skip_cold_stall_limit`` refuses
the skip plan and serves exact.
"""

from __future__ import annotations

import json
import struct
import threading
import urllib.request
import zlib

import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.serve.wire import (
    WireError,
    WireNegotiator,
    WireStats,
    decode_candidates_q16,
    decode_slab_chunk,
    encode_candidates_q16,
    encode_slab_chunk,
    float_to_ordered_u32,
    frame_chunk,
    negotiate,
    ordered_u32_to_float,
    read_frames,
    wire_caps,
    _varint_decode,
    _varint_encode,
    _zigzag,
    _unzigzag,
)

K = 4


# ---------------------------------------------------------- primitives


class TestPrimitives:
    def test_varint_roundtrip(self):
        rng = np.random.default_rng(7)
        vals = np.concatenate([
            np.zeros(3, np.uint64),
            np.array([1, 127, 128, 16383, 16384], np.uint64),
            rng.integers(0, 2 ** 63, 200).astype(np.uint64),
            np.array([np.iinfo(np.uint64).max], np.uint64),
        ])
        raw = _varint_encode(vals)
        out, used = _varint_decode(raw, len(vals))
        assert used == len(raw)
        assert np.array_equal(out, vals)

    def test_varint_empty(self):
        assert _varint_encode(np.zeros(0, np.uint64)) == b""
        out, used = _varint_decode(b"", 0)
        assert used == 0 and out.size == 0

    def test_varint_truncated_raises(self):
        raw = _varint_encode(np.array([300, 300, 300], np.uint64))
        with pytest.raises(WireError, match="truncated"):
            _varint_decode(raw[:-1], 3)

    def test_varint_overlong_raises(self):
        with pytest.raises(WireError, match="overlong"):
            _varint_decode(b"\x80" * 10 + b"\x01", 1)

    def test_zigzag_roundtrip(self):
        v = np.array([0, -1, 1, -2 ** 62, 2 ** 62], np.int64)
        assert np.array_equal(_unzigzag(_zigzag(v)), v)

    def test_ordered_u32_is_exact_and_order_preserving(self):
        rng = np.random.default_rng(11)
        x = np.concatenate([
            rng.normal(size=500), [0.0, -0.0, 1e-38, -1e-38, 3e38, -3e38],
        ]).astype("<f4")
        u = float_to_ordered_u32(x)
        back = ordered_u32_to_float(u)
        # bit-exact inverse (−0.0 maps back to −0.0, hence view compare)
        assert np.array_equal(back.view(np.uint32), x.view(np.uint32))
        # unsigned order == float total order (−0.0 sorts just below
        # +0.0 in u32 space, which float compare calls a tie — so check
        # the float sequence sorted BY u, not u sorted by float)
        assert (np.diff(x[np.argsort(u)]) >= 0).all()


# --------------------------------------------------------- q16 candidates


def _rows(m, k, seed=0, n_valid=None, pad=np.inf):
    """Sorted candidate rows shaped like an engine partial: ascending
    d2 per row, ids a valid prefix, pads a uniform suffix."""
    rng = np.random.default_rng(seed)
    d2 = np.sort(rng.random((m, k)).astype("<f4") * 4.0, axis=1)
    idx = rng.integers(0, 10_000, (m, k)).astype("<i4")
    if n_valid is not None:
        for i, nv in enumerate(n_valid):
            d2[i, nv:] = np.float32(pad)
            idx[i, nv:] = -1
    return d2, idx


class TestQ16Codec:
    def _roundtrip(self, d2, idx):
        payload = encode_candidates_q16(d2, idx)
        assert payload is not None
        m, k = d2.shape
        hi, lo, got_idx = decode_candidates_q16(payload, m, k)
        assert np.array_equal(got_idx, idx)
        valid = idx >= 0
        # THE invariant: quantization ceils, never floors
        assert (hi[valid] >= d2[valid]).all()
        assert (lo[valid] <= d2[valid]).all()
        assert (lo <= hi).all()
        # pad slots ride exact (radius^2 / +inf verbatim)
        assert np.array_equal(hi[~valid], d2[~valid])
        assert np.array_equal(lo[~valid], d2[~valid])
        # the anchor (kth valid) slot is bit-exact — the fold's skip rule
        # and the certification radius both lean on it
        for i in range(m):
            nv = int(valid[i].sum())
            if nv:
                assert hi[i, nv - 1] == d2[i, nv - 1]
                assert lo[i, nv - 1] == d2[i, nv - 1]
        return hi, lo

    def test_full_rows_roundtrip(self):
        self._roundtrip(*_rows(64, K, seed=1))

    def test_k1_rows_are_exact(self):
        d2, idx = _rows(16, 1, seed=2)
        hi, lo = self._roundtrip(d2, idx)
        # every slot is its row's anchor: lossless end to end
        assert np.array_equal(hi, d2) and np.array_equal(lo, d2)

    def test_zero_candidate_rows(self):
        d2, idx = _rows(8, K, seed=3, n_valid=[0, 2, 0, K, 1, 0, 3, K])
        self._roundtrip(d2, idx)

    def test_all_rows_empty(self):
        d2, idx = _rows(4, K, seed=4, n_valid=[0, 0, 0, 0])
        self._roundtrip(d2, idx)

    def test_zero_row_batch(self):
        d2 = np.zeros((0, K), "<f4")
        idx = np.zeros((0, K), "<i4")
        self._roundtrip(d2, idx)

    def test_radius_truncated_rows_keep_finite_pad(self):
        # max_radius-truncated partials pad with radius^2, not +inf
        d2, idx = _rows(8, K, seed=5, n_valid=[2, 3, 1, 4, 2, 2, 3, 1],
                        pad=2.25)
        hi, lo = self._roundtrip(d2, idx)
        assert (hi[idx < 0] == np.float32(2.25)).all()

    def test_interior_slot_in_anchor_band_keeps_sound_lo(self):
        # an interior d2 within 1/65535 of the anchor also ceils to
        # level 65535; it must NOT decode to lo == anchor (the frontend
        # serves a row verbatim when every other contribution's lo
        # strictly exceeds its kth — an overstated lo drops a true
        # neighbor from a row served with exact=True)
        d2 = np.array([[9.99999, 10.0]], "<f4")
        idx = np.array([[1, 2]], "<i4")
        hi, lo = self._roundtrip(d2, idx)
        assert lo[0, 0] <= d2[0, 0]
        assert lo[0, 0] < np.float32(10.0)
        assert hi[0, 0] == np.float32(10.0)  # hi = anchor stays valid

    def test_zero_distance_slots_are_exact(self):
        d2, idx = _rows(4, K, seed=6)
        d2[:, 0] = 0.0  # exact-match neighbor
        hi, lo = self._roundtrip(d2, idx)
        assert (hi[:, 0] == 0.0).all() and (lo[:, 0] == 0.0).all()

    def test_clustered_rows_beat_f32_on_the_wire(self):
        # the codec's reason to exist: Morton-adjacent queries with
        # overlapping neighbor lists must compress well below 8mk
        rng = np.random.default_rng(8)
        m, k = 128, 16
        base = np.sort(rng.random(k).astype("<f4") * 2.0)
        d2 = np.sort(base[None, :]
                     + rng.random((m, k)).astype("<f4") * 1e-3, axis=1)
        idx = (np.arange(m)[:, None] + np.arange(k)[None, :]) \
            .astype("<i4")
        payload = encode_candidates_q16(d2, idx)
        assert payload is not None
        assert len(payload) < 0.45 * 8 * m * k

    def test_encode_refuses_k_over_255(self):
        d2 = np.zeros((2, 256), "<f4")
        idx = np.zeros((2, 256), "<i4")
        assert encode_candidates_q16(d2, idx) is None

    def test_encode_refuses_nan(self):
        d2, idx = _rows(4, K, seed=9)
        d2[1, 2] = np.nan
        assert encode_candidates_q16(d2, idx) is None

    def test_encode_refuses_non_prefix_pads(self):
        d2, idx = _rows(4, K, seed=10)
        idx[0, 1] = -1  # hole in the middle of a row
        assert encode_candidates_q16(d2, idx) is None

    def test_encode_refuses_non_uniform_pad(self):
        d2, idx = _rows(4, K, seed=11, n_valid=[2, 2, 2, 2])
        d2[0, 3] = 7.0  # two different pad distances
        assert encode_candidates_q16(d2, idx) is None

    def test_decode_rejects_shape_mismatch(self):
        d2, idx = _rows(4, K, seed=12)
        payload = encode_candidates_q16(d2, idx)
        with pytest.raises(WireError, match="mismatch"):
            decode_candidates_q16(payload, 5, K)
        with pytest.raises(WireError, match="mismatch"):
            decode_candidates_q16(payload, 4, K + 1)

    def test_decode_rejects_garbage_and_truncation(self):
        with pytest.raises(WireError):
            decode_candidates_q16(b"not zlib at all", 4, K)
        d2, idx = _rows(4, K, seed=13)
        body = zlib.decompress(encode_candidates_q16(d2, idx))
        with pytest.raises(WireError):
            decode_candidates_q16(zlib.compress(body[:-3]), 4, K)


# --------------------------------------------------------- d16 slab codec


def _morton_points(n, seed=0, scale=1.0):
    from mpi_cuda_largescaleknn_tpu.utils.math import morton_argsort

    rng = np.random.default_rng(seed)
    pts = (rng.random((n, 3)).astype(np.float32) * np.float32(scale))
    if n == 0:
        return np.zeros((0, 3), "<f4")
    order = morton_argsort(pts, pts.min(axis=0), pts.max(axis=0))
    return np.ascontiguousarray(pts[order], "<f4")


class TestD16Codec:
    @pytest.mark.parametrize("n", [0, 1, 2, 257])
    def test_lossless_roundtrip(self, n):
        pts = _morton_points(n, seed=n)
        out = decode_slab_chunk(encode_slab_chunk(pts), n, 3)
        assert np.array_equal(out.view(np.uint32), pts.view(np.uint32))

    def test_negative_coordinates_roundtrip(self):
        pts = _morton_points(128, seed=20) - np.float32(0.5)
        out = decode_slab_chunk(encode_slab_chunk(pts), 128, 3)
        assert np.array_equal(out.view(np.uint32), pts.view(np.uint32))

    def test_sign_crossing_magnitude_gt1_roundtrip(self):
        # consecutive rows crossing zero at |coord| > ~1 produce
        # zigzag'd ordered-u32 steps up to ~2^33: the width ladder must
        # widen to 8-byte planes instead of silently truncating to u32
        rng = np.random.default_rng(24)
        pts = (rng.random((4096, 3)).astype("<f4")
               * np.float32(6.0) - np.float32(3.0)).astype("<f4")
        out = decode_slab_chunk(encode_slab_chunk(pts), 4096, 3)
        assert np.array_equal(out.view(np.uint32), pts.view(np.uint32))
        # the adversarial pair alone: one maximal sign-crossing step
        pair = np.array([[-3.0, -3e38, 1e-38],
                         [3.0, 3e38, -1e-38]], "<f4")
        out = decode_slab_chunk(encode_slab_chunk(pair), 2, 3)
        assert np.array_equal(out.view(np.uint32), pair.view(np.uint32))

    def test_morton_sorted_rows_compress(self):
        pts = _morton_points(4096, seed=21, scale=0.01)
        enc = encode_slab_chunk(pts)
        assert enc[0] == 1  # took the delta path, not raw
        assert len(enc) < 0.8 * pts.nbytes

    def test_raw_fallback_chunk_decodes(self):
        pts = _morton_points(32, seed=22)
        raw = b"\x00" + pts.tobytes()
        out = decode_slab_chunk(raw, 32, 3)
        assert np.array_equal(out, pts)

    def test_decode_rejects_bad_payloads(self):
        pts = _morton_points(512, seed=23, scale=0.01)
        enc = encode_slab_chunk(pts)
        assert enc[0] == 1  # compressible fixture → delta path
        with pytest.raises(WireError):
            decode_slab_chunk(b"", 512, 3)
        with pytest.raises(WireError, match="flag"):
            decode_slab_chunk(b"\x07" + enc[1:], 512, 3)
        with pytest.raises(WireError, match="mismatch"):
            decode_slab_chunk(enc, 511, 3)
        with pytest.raises(WireError, match="mismatch"):
            decode_slab_chunk(enc, 512, 4)
        with pytest.raises(WireError):
            decode_slab_chunk(b"\x00" + pts.tobytes()[:-4], 512, 3)


class TestFraming:
    def _stream(self, chunks):
        buf = b"".join(chunks)
        pos = [0]

        def read(n):
            got = buf[pos[0]:pos[0] + n]
            pos[0] += len(got)
            return got

        return read

    def test_multi_frame_roundtrip(self):
        pts = _morton_points(100, seed=30)
        chunks = [frame_chunk(40, encode_slab_chunk(pts[:40])),
                  frame_chunk(40, encode_slab_chunk(pts[40:80])),
                  frame_chunk(20, encode_slab_chunk(pts[80:]))]
        parts = [decode_slab_chunk(payload, rows, 3)
                 for rows, payload in
                 read_frames(self._stream(chunks), 100)]
        out = np.concatenate(parts)
        assert np.array_equal(out.view(np.uint32), pts.view(np.uint32))

    def test_torn_stream_raises_not_truncates(self):
        pts = _morton_points(100, seed=31)
        whole = (frame_chunk(40, encode_slab_chunk(pts[:40]))
                 + frame_chunk(60, encode_slab_chunk(pts[40:])))
        for cut in (4, len(whole) // 2, len(whole) - 1):
            read = self._stream([whole[:cut]])
            with pytest.raises(WireError, match="torn|wanted"):
                list(read_frames(read, 100))

    def test_overflowing_frame_raises(self):
        payload = encode_slab_chunk(_morton_points(60, seed=32))
        read = self._stream([frame_chunk(60, payload)])
        with pytest.raises(WireError, match="bad slab frame"):
            list(read_frames(read, 40))

    def test_zero_row_frame_raises(self):
        read = self._stream([struct.pack("<II", 0, 0)])
        with pytest.raises(WireError, match="bad slab frame"):
            list(read_frames(read, 10))


# ----------------------------------------------------------- negotiation


class TestNegotiation:
    def test_caps_tables(self):
        assert wire_caps() == {"candidates": ["q16", "f32"],
                               "slab_rows": ["d16", "f32"]}
        assert wire_caps("f32") == {"candidates": ["f32"],
                                    "slab_rows": ["f32"]}

    def test_negotiate_matrix(self):
        full = wire_caps()
        assert negotiate("auto", full, "candidates") == "q16"
        assert negotiate("auto", full, "slab_rows") == "d16"
        assert negotiate("q16", full, "candidates") == "q16"
        # mismatches all fall back, never raise
        assert negotiate("f32", full, "candidates") == "f32"
        assert negotiate("auto", None, "candidates") == "f32"
        assert negotiate("auto", {}, "slab_rows") == "f32"
        assert negotiate("auto", wire_caps("f32"), "candidates") == "f32"
        assert negotiate("q16", full, "slab_rows") == "f32"

    def test_negotiator_table(self):
        neg = WireNegotiator("auto")
        neg.set_caps("http://a:1/", wire_caps())
        neg.set_caps("http://b:2", None)  # old binary
        assert neg.codec_for("http://a:1") == "q16"
        assert neg.codec_for("http://a:1/", "slab_rows") == "d16"
        assert neg.codec_for("http://b:2") == "f32"
        assert neg.codec_for("http://never-seen:9") == "f32"
        snap = neg.snapshot()
        assert snap["mode"] == "auto"
        assert snap["negotiated"]["http://b:2"]["candidates"] == "f32"

    def test_negotiator_rejects_bad_mode(self):
        with pytest.raises(ValueError, match="wire mode"):
            WireNegotiator("brotli")

    def test_wire_stats_accounting(self):
        st = WireStats()
        st.add("candidates", "q16", 100, 10)
        st.add("candidates", "q16", 50, 10)
        st.add("slab_rows", "d16", 999)
        snap = st.snapshot()
        assert snap["candidates"]["q16"] == {
            "bytes": 150, "rows": 20, "bytes_per_row": 7.5}
        assert "bytes_per_row" not in snap["slab_rows"]["d16"]
        lines = st.prometheus_lines()
        assert ('knn_wire_bytes_total{path="candidates",codec="q16"} 150'
                in lines)
        assert ('knn_wire_bytes_per_row{path="candidates",codec="q16"} '
                '7.5' in lines)


# --------------------------------------------------- HTTP host integration


def _boot(engine, **kw):
    from mpi_cuda_largescaleknn_tpu.serve.frontend import HostSliceServer

    srv = HostSliceServer(("127.0.0.1", 0), engine, routing="bounds",
                          **kw)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    srv.ready = True
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


@pytest.fixture(scope="module")
def wire_hosts():
    """ONE small candidate-emitting slab engine behind two servers: a
    ``wire="auto"`` host and a ``wire="f32"`` host (the supported way to
    emulate an old binary) — same engine, so every difference on the
    wire is the codec's doing."""
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

    pts = _morton_points(256, seed=40)
    eng = ResidentKnnEngine(pts, K, mesh=get_mesh(2), engine="tiled",
                            bucket_size=32, max_batch=32, min_batch=8,
                            emit="candidates")
    eng.warmup()
    auto_srv, auto_url = _boot(eng)
    f32_srv, f32_url = _boot(eng, wire="f32")
    yield pts, auto_url, f32_url
    auto_srv.close()
    f32_srv.close()


def _post_route(url, q, wire=None):
    qs = f"?wire={wire}" if wire else ""
    req = urllib.request.Request(
        url + "/route_knn" + qs, data=np.ascontiguousarray(q, "<f4")
        .tobytes(), headers={"Content-Type": "application/octet-stream"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.headers.get("X-Knn-Wire"), r.read()


def _queries(pts, m=12, seed=50):
    rng = np.random.default_rng(seed)
    return (pts[rng.integers(0, len(pts), m)]
            + rng.normal(scale=1e-3, size=(m, 3)).astype(np.float32))


class TestHostWireHttp:
    def test_stats_advertise_caps_at_root(self, wire_hosts):
        _pts, auto_url, f32_url = wire_hosts
        for url, mode in ((auto_url, "auto"), (f32_url, "f32")):
            with urllib.request.urlopen(url + "/stats", timeout=30) as r:
                stats = json.loads(r.read())
            assert stats["wire"] == wire_caps(mode)
            # deliberately OUTSIDE the engine sub-dict: replica
            # fingerprints must not move when a codec is added
            assert "wire" not in stats.get("engine", {})

    def test_legacy_request_gets_precodec_body(self, wire_hosts):
        pts, auto_url, f32_url = wire_hosts
        q = _queries(pts)
        wire, body = _post_route(auto_url, q)
        assert wire is None
        assert len(body) == 8 * len(q) * K
        # and the f32-only host serves the very same bytes
        wire2, body2 = _post_route(f32_url, q)
        assert wire2 is None and body2 == body

    def test_q16_brackets_the_f32_answer(self, wire_hosts):
        pts, auto_url, _ = wire_hosts
        q = _queries(pts)
        m = len(q)
        _, f32_body = _post_route(auto_url, q)
        d2 = np.frombuffer(f32_body, "<f4", count=m * K).reshape(m, K)
        idx = np.frombuffer(f32_body, "<i4", count=m * K,
                            offset=4 * m * K).reshape(m, K)
        wire, body = _post_route(auto_url, q, wire="q16")
        assert wire == "q16"
        assert len(body) < len(f32_body)
        hi, lo, got_idx = decode_candidates_q16(body, m, K)
        assert np.array_equal(got_idx, idx)
        valid = idx >= 0
        assert (hi[valid] >= d2[valid]).all()
        assert (lo[valid] <= d2[valid]).all()

    def test_q16_ask_to_f32_host_is_a_clean_fallback(self, wire_hosts):
        """The codec-mismatch bar: an f32-only host answers a ?wire=q16
        ask with the plain f32 body and no codec header — the response
        header selects the parse, so the caller never hits a decode
        error, it just reads uncompressed rows."""
        pts, auto_url, f32_url = wire_hosts
        q = _queries(pts)
        wire, body = _post_route(f32_url, q, wire="q16")
        assert wire is None
        assert len(body) == 8 * len(q) * K
        _, ref = _post_route(auto_url, q)
        assert body == ref

    def test_x32_refetch_carries_exact_d2(self, wire_hosts):
        pts, auto_url, _ = wire_hosts
        q = _queries(pts)
        m = len(q)
        _, f32_body = _post_route(auto_url, q)
        wire, body = _post_route(auto_url, q, wire="x32")
        assert wire == "x32"
        assert len(body) == 4 * m * K
        assert body == f32_body[:4 * m * K]

    def test_slab_pull_codecs_are_lossless(self, wire_hosts):
        from mpi_cuda_largescaleknn_tpu.serve.replica import pull_slab_rows

        pts, auto_url, f32_url = wire_hosts
        for wire in ("d16", "f32", "none"):  # "none" = legacy single-shot
            rows, off = pull_slab_rows(auto_url, wire=wire)
            assert off == 0
            assert np.array_equal(rows.view(np.uint32),
                                  pts.view(np.uint32)), wire
        # an f32-mode host streams chunked f32 — still lossless
        rows, _ = pull_slab_rows(f32_url, wire="d16")
        assert np.array_equal(rows.view(np.uint32), pts.view(np.uint32))

    def test_slab_pull_subrange(self, wire_hosts):
        from mpi_cuda_largescaleknn_tpu.serve.replica import pull_slab_rows

        pts, auto_url, _ = wire_hosts
        rows, off = pull_slab_rows(auto_url, begin=17, end=101)
        assert off == 17
        assert np.array_equal(rows.view(np.uint32),
                              pts[17:101].view(np.uint32))

    @pytest.mark.parametrize("wire", ["d16", "f32"])
    def test_multi_frame_slab_pull_is_lossless(self, wire_hosts,
                                               monkeypatch, wire):
        """A slab larger than one stream chunk arrives as several frames
        and still decodes to the host's rows bit for bit."""
        from mpi_cuda_largescaleknn_tpu.serve.frontend import _HostHandler
        from mpi_cuda_largescaleknn_tpu.serve.replica import pull_slab_rows

        pts, auto_url, _ = wire_hosts
        monkeypatch.setattr(_HostHandler, "slab_chunk_rows", 60)
        rows, off = pull_slab_rows(auto_url, wire=wire)
        assert off == 0 and -(-len(pts) // 60) > 1
        assert np.array_equal(rows.view(np.uint32), pts.view(np.uint32))


# ------------------------------------------ front end: codec vs f32 wire

WIRE_POD_K = 16


@pytest.fixture(scope="module")
def wire_pods():
    """Slab hosts for four pod shapes over one Morton-sorted point set,
    with four rows copied across the slab boundary (distance-0 ties
    between hosts): plain routed, replicated, one host streaming four
    sub-slabs, and one host forced to the f32 wire."""
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
    from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
        StreamingKnnEngine,
    )

    pts = _morton_points(2048, seed=70)
    half = len(pts) // 2
    pts[half:half + 4] = pts[half - 4:half]
    kw = dict(mesh=get_mesh(1), engine="tiled", bucket_size=64,
              max_batch=32, min_batch=16, emit="candidates")
    eng0 = ResidentKnnEngine(pts[:half], WIRE_POD_K, id_offset=0, **kw)
    eng1 = ResidentKnnEngine(pts[half:], WIRE_POD_K, id_offset=half, **kw)
    stream0 = StreamingKnnEngine(points=pts[:half], num_slabs=4,
                                 k=WIRE_POD_K, id_offset=0, **kw)
    for e in (eng0, eng1, stream0):
        e.warmup()
    servers, urls = {}, {}
    for name, eng, skw in (("a0", eng0, {}), ("a0b", eng0, {}),
                           ("a1", eng1, {}), ("a1b", eng1, {}),
                           ("s0", stream0, {}), ("f1", eng1,
                                                 {"wire": "f32"})):
        servers[name], urls[name] = _boot(eng, **skw)
    pods = {"routed": ["a0", "a1"],
            "replicated": ["a0", "a0b", "a1", "a1b"],
            "streaming": ["s0", "a1"],
            "mixed_f32_host": ["a0", "f1"]}
    rng = np.random.default_rng(71)
    centers = rng.random((4, 3))
    probe = np.concatenate([
        pts[half - 4:half + 4],
        np.clip(centers[rng.integers(4, size=12)]
                + rng.normal(0, 0.02, (12, 3)), 0, 1),
        rng.random((12, 3)),
    ]).astype(np.float32)
    yield {n: [urls[h] for h in hosts] for n, hosts in pods.items()}, probe
    for srv in servers.values():
        srv.close()
    stream0.close()


def _probe_frontend(urls, probe, wire):
    """One probe through a fresh front end at ``wire``; the JSON answer
    and the front end's candidate-exchange traffic by codec."""
    from mpi_cuda_largescaleknn_tpu.serve.frontend import build_frontend

    fe = build_frontend(urls, port=0, max_delay_s=0.002, pipeline_depth=2,
                        wire=wire, qcache_rows=0, start_monitor=False)
    fe.ready = True
    threading.Thread(target=fe.serve_forever, daemon=True).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{fe.server_address[1]}/knn",
            data=json.dumps({"queries": probe.tolist(),
                             "neighbors": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            resp = json.loads(r.read())
        traffic = fe.fanout.stats()["wire"]["traffic"].get("candidates",
                                                            {})
    finally:
        fe.close()
    return resp, traffic


class TestFrontendWireParity:
    @pytest.mark.parametrize("pod", ["routed", "replicated", "streaming",
                                     "mixed_f32_host"])
    def test_codec_answers_equal_f32_wire(self, wire_pods, pod):
        """Negotiated codecs change bytes on the wire, never the answer:
        dists, neighbour ids (the cross-host distance-0 ties included)
        and exact flags equal the f32 wire's, on every pod shape."""
        pods, probe = wire_pods
        f32, f32_traffic = _probe_frontend(pods[pod], probe, "f32")
        auto, auto_traffic = _probe_frontend(pods[pod], probe, "auto")
        assert set(f32_traffic) == {"f32"}
        assert "q16" in auto_traffic
        assert auto["dists"] == f32["dists"]
        assert auto["neighbors"] == f32["neighbors"]
        assert auto.get("exact", True) is True
        assert f32.get("exact", True) is True

    def test_q16_candidate_bytes_per_row_under_045_of_f32(self, wire_pods):
        """The compressed exchange's reason to exist, counted in bytes:
        q16 candidate rows cost at most 0.45 x the f32 rows."""
        pods, probe = wire_pods
        _f32, f32_traffic = _probe_frontend(pods["routed"], probe, "f32")
        _auto, auto_traffic = _probe_frontend(pods["routed"], probe, "auto")
        f32_bpr = f32_traffic["f32"]["bytes"] / f32_traffic["f32"]["rows"]
        q16_bpr = (auto_traffic["q16"]["bytes"]
                   / auto_traffic["q16"]["rows"])
        assert f32_bpr == 8 * WIRE_POD_K
        assert q16_bpr <= 0.45 * f32_bpr


# ------------------------------------------- skip-cold drift admission


class _FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


@pytest.fixture()
def drift_stream():
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
        StreamingKnnEngine,
    )

    clock = _FakeClock()
    stream = StreamingKnnEngine(points=_morton_points(128, seed=60),
                                num_slabs=2, k=2, mesh=get_mesh(2),
                                engine="tiled", bucket_size=16,
                                max_batch=16, min_batch=4,
                                clock=clock)
    yield stream, clock
    stream.close()


class TestSkipColdDriftAdmission:
    def _stall(self, stream, seconds):
        """Pin the pool's cumulative stall clock to a chosen value."""
        stream._pool.stall_totals = (
            lambda tenant=None: (1, float(seconds)))

    def test_healthy_pool_admits(self, drift_stream):
        stream, clock = drift_stream
        self._stall(stream, 0.0)
        for _ in range(5):
            clock.t += 1.0
            assert stream._skip_cold_admit()
        assert stream.skip_cold_refusals == 0

    def test_stalling_pool_refuses_then_readmits(self, drift_stream):
        stream, clock = drift_stream
        # 10s of wall, no stalls: healthy baseline in the ring
        self._stall(stream, 0.0)
        for _ in range(10):
            clock.t += 1.0
            assert stream._skip_cold_admit()
        # now every wall second is ~50% stall — far above the 0.25 limit
        stall = 0.0
        refused = 0
        for _ in range(30):
            clock.t += 1.0
            stall += 0.5
            self._stall(stream, stall)
            if not stream._skip_cold_admit():
                refused += 1
        assert refused > 0
        assert stream.skip_cold_refusals == refused
        # the stalls stop; once the window drains the tier re-opens
        self._stall(stream, stall)
        admitted = False
        for _ in range(2 * stream.skip_cold_window):
            clock.t += 1.0
            if stream._skip_cold_admit():
                admitted = True
                break
        assert admitted, "admission never recovered after stalls ceased"

    def test_refused_plan_serves_exact(self, drift_stream):
        from mpi_cuda_largescaleknn_tpu.serve.recall import RecallPlan

        stream, clock = drift_stream
        q = _morton_points(8, seed=61)
        exact = [np.asarray(x) for x in stream.query(q)]
        # poison the window: 100% stall fraction
        stall = 0.0
        for _ in range(10):
            clock.t += 1.0
            stall += 1.0
            self._stall(stream, stall)
            stream._skip_cold_admit()
        before = stream.skip_cold_refusals
        assert before > 0
        plan = RecallPlan(name="drifty", stream_skip_cold=True,
                          recall_estimated=0.9)
        d, i = stream.query(q, plan=plan)
        # the plan was refused (counted) and the batch served exact
        assert stream.skip_cold_refusals > before
        assert np.array_equal(np.asarray(d), exact[0])
        assert np.array_equal(np.asarray(i), exact[1])
        assert stream.stats()["streaming"]["skip_cold_refusals"] \
            == stream.skip_cold_refusals

    def test_stats_surface_the_knobs(self, drift_stream):
        stream, _clock = drift_stream
        s = stream.stats()["streaming"]
        assert s["skip_cold_stall_limit"] == pytest.approx(0.25)
        assert "skip_cold_refusals" in s
