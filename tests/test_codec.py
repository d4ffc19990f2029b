"""Wire codec (utils/codec.py + cpp ps_lz_*): roundtrip fuzz, malformed-
frame safety, and cross-codec interop (role of the reference's snappy
CompressTo/UncompressFrom, shared_array_inl.h)."""

import numpy as np
import pytest

from parameter_server_tpu.cpp import native
from parameter_server_tpu.utils import codec


def _payloads(rng):
    yield b""
    yield b"x"
    yield b"abcd" * 3  # 12 bytes: below the n>12 match threshold
    yield b"\x00" * 100000  # RLE (offset-1 overlap copies)
    yield bytes(rng.integers(0, 256, 1 << 16, dtype=np.uint8))  # noise
    yield (b"the quick brown fox " * 4000)  # highly repetitive
    g = rng.normal(size=1 << 16).astype(np.float32)
    g[rng.random(g.size) < 0.9] = 0.0
    yield g.tobytes()  # sparse float gradients
    yield np.arange(1 << 14, dtype=np.int64).tobytes()  # sorted keys
    # periodic patterns around the 8-byte overlap-copy boundary
    for period in (1, 2, 3, 5, 7, 8, 9, 15, 16, 17):
        yield bytes(range(period)) * (3000 // period)


class TestRoundtrip:
    def test_representative_payloads(self):
        rng = np.random.default_rng(0)
        for data in _payloads(rng):
            frame = codec.compress(data)
            assert codec.decompress(frame) == data

    def test_random_mutation_fuzz(self):
        """500 random payloads roundtrip; mutated FRAMES must either
        decode to something or raise ValueError — never crash, hang, or
        over-allocate (malformed input is distinguished from
        small-output, so garbage can't trigger buffer growth)."""
        rng = np.random.default_rng(1)
        for _ in range(500):
            n = int(rng.integers(0, 5000))
            if rng.random() < 0.5:
                data = bytes(rng.integers(0, 256, n, dtype=np.uint8))
            else:  # compressible: few symbols + runs
                data = bytes(
                    rng.choice([0, 1, 65], p=[0.7, 0.2, 0.1], size=n)
                    .astype(np.uint8)
                )
            frame = codec.compress(data)
            assert codec.decompress(frame) == data
            # mutate the frame
            fb = bytearray(frame)
            for _ in range(int(rng.integers(1, 4))):
                op = rng.integers(0, 3)
                if op == 0 and len(fb) > 1:
                    fb[int(rng.integers(0, len(fb)))] = int(
                        rng.integers(0, 256)
                    )
                elif op == 1 and len(fb) > 2:
                    del fb[int(rng.integers(1, len(fb))):]
                else:
                    fb.insert(
                        int(rng.integers(0, len(fb) + 1)),
                        int(rng.integers(0, 256)),
                    )
            try:
                codec.decompress(bytes(fb), max_size=1 << 24)
            except ValueError:
                pass  # rejection is the expected failure mode

    def test_zlib_fallback_interop(self, monkeypatch):
        """A zlib frame (native-less sender) decodes on a native host,
        and RAW frames decode everywhere."""
        import zlib

        data = b"payload " * 1000
        zframe = bytes([2]) + zlib.compress(data, 1)
        assert codec.decompress(zframe) == data
        assert codec.decompress(bytes([0]) + data) == data

    def test_malformed_rejections(self):
        with pytest.raises(ValueError):
            codec.decompress(b"")
        with pytest.raises(ValueError):
            codec.decompress(bytes([9]) + b"zz")  # unknown tag
        with pytest.raises(ValueError):
            codec.decompress(bytes([2]) + b"notzlib")
        # truncated LZ: token promises literals that aren't there
        with pytest.raises(ValueError):
            codec.decompress(bytes([1, 0xF0, 255, 255]))


class TestNativeEdges:
    def test_incompressible_stays_raw(self):
        rng = np.random.default_rng(2)
        data = bytes(rng.integers(0, 256, 4096, dtype=np.uint8))
        frame = codec.compress(data)
        assert frame[0] == 0 and len(frame) == len(data) + 1

    def test_compression_wins_on_sparse_values(self):
        g = np.zeros(1 << 16, np.float32)
        g[::97] = 1.5
        frame = codec.compress(g.tobytes())
        assert frame[0] == 1
        assert len(frame) < g.nbytes // 10


def _require_native():
    from conftest import require_native

    return require_native()


class TestHostileBuffers:
    """The staging-leg codec's untrusted-input contract, exercised
    through BOTH the native LZ path and the zlib fallback: empty
    frames, incompressible noise, and length-extension headers claiming
    multi-GB output (the >4GB-frame-header edge) must round-trip or
    reject cleanly — never crash, hang, or allocate the claimed size."""

    HOSTILE = (
        b"",  # empty
        b"\x00",  # single byte
        bytes(np.random.default_rng(7).integers(0, 256, 1 << 15,
                                                dtype=np.uint8)),
        b"\xff" * 70000,  # long RLE run (length extensions on encode)
    )

    def _roundtrip_all(self):
        for data in self.HOSTILE:
            frame = codec.compress(data)
            assert codec.decompress(frame) == data
            # incompressible noise must ride raw, not expand
            assert len(frame) <= len(data) + 1 + len(data) // 255 + 16

    def test_native_path(self):
        _require_native()
        self._roundtrip_all()

    def test_zlib_fallback_path(self, monkeypatch):
        monkeypatch.setattr(codec, "native", lambda: None)
        self._roundtrip_all()
        # and a zlib frame produced here still decodes with native back
        monkeypatch.undo()
        data = self.HOSTILE[2]
        import zlib

        assert codec.decompress(bytes([2]) + zlib.compress(data, 1)) == data

    def test_lz_giant_claim_rejected_without_allocation(self):
        """An LZ frame whose 255-run match-length extensions claim far
        more output than max_size must raise, not allocate the claim:
        the grow loop is capped at max_size (the >4GB header edge,
        scaled down — the code path is the same -2/grow/cap one)."""
        _require_native()
        # token: 4 literals + match-len 15 (extensions follow); then
        # literals, offset=1, and a run of 255-extensions claiming ~2MB
        frame = bytes([1, (4 << 4) | 15]) + b"abcd" + bytes([1, 0]) + (
            b"\xff" * 8000
        ) + bytes([7])
        with pytest.raises(ValueError):
            codec.decompress(frame, max_size=1 << 16)

    def test_zlib_bomb_bounded_by_max_size(self, monkeypatch):
        """The zlib fallback must bound output BEFORE the bytes exist
        (decompressobj max_length, not the one-shot API): a tiny frame
        claiming 64MB of zeros stops at max_size."""
        import zlib

        bomb = bytes([2]) + zlib.compress(b"\x00" * (64 << 20), 1)
        assert len(bomb) < 1 << 20
        with pytest.raises(ValueError):
            codec.decompress(bomb, max_size=1 << 16)

    def test_expected_size_oversized_clamped(self):
        data = b"q" * 4096
        frame = codec.compress(data)
        # a wildly wrong expected_size must not pre-allocate past
        # max_size, and a CORRECT decode still comes back
        assert codec.decompress(frame, expected_size=1 << 62) == data
