"""Codec layer tests: roundtrip correctness + cross-impl byte equality.

Determinism here is the whole game (SURVEY.md §7 hard parts #2): the PNG/MP4
bytes feed straight into the CID the miner commits on-chain. So every codec
is tested three ways: (1) structural validity via an independent decoder
(stdlib zlib inflate, PIL), (2) byte-stability across calls, and (3) the
native C++ deflate against the pure-Python spec implementation.
"""
from __future__ import annotations

import io
import zlib

import numpy as np
import pytest

from arbius_tpu.codecs import (
    deflate_compress,
    deflate_fixed,
    encode_jpeg,
    encode_mp4,
    encode_png,
    zlib_compress,
)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _test_image(h=64, w=64, seed=0):
    """Natural-ish gradient + noise image, not pure noise."""
    yy, xx = np.mgrid[0:h, 0:w]
    base = np.stack([xx * 255 // max(w - 1, 1),
                     yy * 255 // max(h - 1, 1),
                     (xx + yy) * 255 // max(h + w - 2, 1)], axis=-1)
    noise = _rng(seed).integers(0, 32, (h, w, 3))
    return np.clip(base + noise, 0, 255).astype(np.uint8)


# -- deflate ---------------------------------------------------------------

DEFLATE_CASES = [
    b"",
    b"a",
    b"abc",
    b"aaaaaaaaaaaaaaaaaaaaaaaaaaaa",
    b"the quick brown fox jumps over the lazy dog" * 50,
    bytes(range(256)) * 10,
    _rng(1).integers(0, 256, 70000).astype(np.uint8).tobytes(),
    (b"\x00" * 300000),          # multi-window RLE
]


@pytest.mark.parametrize("data", DEFLATE_CASES, ids=range(len(DEFLATE_CASES)))
def test_deflate_roundtrip(data):
    comp = deflate_fixed(data)
    assert zlib.decompress(comp, wbits=-15) == data


@pytest.mark.parametrize("data", DEFLATE_CASES, ids=range(len(DEFLATE_CASES)))
def test_native_matches_python(data):
    from arbius_tpu.codecs import _native

    fn = _native.deflate_fixed()
    if fn is None:
        pytest.skip("native codec lib unavailable (no g++?)")
    assert fn(data) == deflate_fixed(data)


@pytest.fixture
def fresh_native(monkeypatch, tmp_path):
    """_native with its one-shot load state reset and its build dir in
    tmp_path (the module caches the first verdict per process)."""
    from arbius_tpu.codecs import _native

    monkeypatch.setattr(_native, "_tried", False)
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_BUILD_DIR", str(tmp_path / "build"))
    return _native


def test_native_library_is_named_by_its_source_hash(fresh_native, tmp_path):
    """A binary built from another codecs.cc can never load: the name
    carries the source digest, so a stale one is simply not looked at."""
    import hashlib
    import os

    stale = tmp_path / "build" / "libarbius_codecs.so"
    stale.parent.mkdir()
    stale.write_bytes(b"not even ELF")       # the pre-hash file name
    if fresh_native.deflate_impl() != "native":
        pytest.skip("native codec lib unavailable (no g++?)")
    with open(fresh_native._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert sorted(os.listdir(tmp_path / "build")) == [
        f"libarbius_codecs.{digest}.so", "libarbius_codecs.so"]


def test_failed_native_build_is_logged_once_with_the_compiler_error(
        fresh_native, monkeypatch, tmp_path, caplog):
    bad = tmp_path / "codecs.cc"
    bad.write_text("this is not C++;\n")
    monkeypatch.setattr(fresh_native, "_SRC", str(bad))
    with caplog.at_level("WARNING", logger="arbius.codecs"):
        assert fresh_native.deflate_impl() == "python"
        assert fresh_native.deflate_fixed() is None
        assert deflate_compress(b"abc" * 100)    # python path still serves
    logged = [r.getMessage() for r in caplog.records
              if r.name == "arbius.codecs"]
    assert len(logged) == 1
    assert "codecs.cc" in logged[0] and "error" in logged[0]


def test_zlib_container_valid():
    data = b"hello arbius" * 100
    assert zlib.decompress(zlib_compress(data)) == data


def test_deflate_compresses_repetitive_data():
    data = b"abcdef" * 10000
    assert len(deflate_compress(data)) < len(data) // 10


# -- png -------------------------------------------------------------------

def test_png_decodes_exactly():
    PIL = pytest.importorskip("PIL.Image")
    img = _test_image(48, 80)
    png = encode_png(img)
    decoded = np.asarray(PIL.open(io.BytesIO(png)).convert("RGB"))
    np.testing.assert_array_equal(decoded, img)


def test_png_deterministic():
    img = _test_image(32, 32, seed=7)
    assert encode_png(img) == encode_png(img.copy())


def test_png_rejects_bad_input():
    with pytest.raises(ValueError):
        encode_png(np.zeros((8, 8, 4), np.uint8))
    with pytest.raises(ValueError):
        encode_png(np.zeros((8, 8, 3), np.float32))


def test_png_golden_stability():
    """Pin the exact bytes of a small image: any change to the filter
    choice, deflate parameters, or chunk layout is a determinism-class
    break and must be a deliberate, versioned decision."""
    img = _test_image(16, 16, seed=3)
    import hashlib
    digest = hashlib.sha256(encode_png(img)).hexdigest()
    assert encode_png(img)[:8] == b"\x89PNG\r\n\x1a\n"
    assert digest == ("eef2e774ae4507ab3f55b1c4072453b5"
                      "05fd8b20cc74978a5ac2fbe81c9351f6"), digest


# -- jpeg ------------------------------------------------------------------

def test_jpeg_decodes_close():
    PIL = pytest.importorskip("PIL.Image")
    img = _test_image(64, 64, seed=5)
    jpg = encode_jpeg(img, quality=90)
    decoded = np.asarray(PIL.open(io.BytesIO(jpg)).convert("RGB"))
    assert decoded.shape == img.shape
    err = np.abs(decoded.astype(np.int32) - img.astype(np.int32))
    assert float(err.mean()) < 6.0, float(err.mean())


def test_jpeg_deterministic():
    img = _test_image(24, 40, seed=9)
    assert encode_jpeg(img) == encode_jpeg(img.copy())


def test_jpeg_quality_monotonic():
    img = _test_image(64, 64, seed=2)
    assert len(encode_jpeg(img, quality=95)) > len(encode_jpeg(img, quality=30))


def test_jpeg_flat_image_tiny():
    img = np.full((32, 32, 3), 128, np.uint8)
    assert len(encode_jpeg(img)) < 1200


# -- mp4 -------------------------------------------------------------------

def _parse_boxes(data: bytes):
    out = []
    off = 0
    while off < len(data):
        size = int.from_bytes(data[off:off + 4], "big")
        tag = data[off + 4:off + 8]
        out.append((tag, data[off + 8:off + size]))
        off += size
    return out


def test_mp4_structure():
    frames = np.stack([_test_image(32, 48, seed=i) for i in range(4)])
    mp4 = encode_mp4(frames, fps=8)
    boxes = _parse_boxes(mp4)
    assert [t for t, _ in boxes] == [b"ftyp", b"mdat", b"moov"]
    mdat = boxes[1][1]
    # each sample is a standalone JPEG inside mdat
    assert mdat[:2] == b"\xff\xd8"
    moov = dict(_parse_boxes(boxes[2][1]))
    assert b"mvhd" in moov and b"trak" in moov


def test_mp4_sample_offsets_point_at_jpegs():
    frames = np.stack([_test_image(16, 16, seed=i) for i in range(3)])
    mp4 = encode_mp4(frames, fps=4)
    # find stco inside the box tree and check each offset hits an SOI marker
    idx = mp4.find(b"stco")
    assert idx > 0
    n = int.from_bytes(mp4[idx + 8:idx + 12], "big")
    assert n == 3
    for i in range(n):
        off = int.from_bytes(mp4[idx + 12 + 4 * i:idx + 16 + 4 * i], "big")
        assert mp4[off:off + 2] == b"\xff\xd8"


def test_mp4_deterministic():
    frames = np.stack([_test_image(16, 24, seed=i) for i in range(2)])
    assert encode_mp4(frames) == encode_mp4(frames.copy())


def test_mp4_decodable_if_ffmpeg_present():
    import shutil
    import subprocess
    import tempfile

    if shutil.which("ffprobe") is None:
        pytest.skip("ffprobe not installed")
    frames = np.stack([_test_image(32, 32, seed=i) for i in range(4)])
    with tempfile.NamedTemporaryFile(suffix=".mp4") as f:
        f.write(encode_mp4(frames, fps=8))
        f.flush()
        out = subprocess.run(
            ["ffprobe", "-v", "error", "-show_entries",
             "stream=codec_name,nb_frames", "-of", "csv=p=0", f.name],
            capture_output=True, text=True)
        assert out.returncode == 0, out.stderr
        assert "mjpeg" in out.stdout
