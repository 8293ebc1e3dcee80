"""Native host runtime + data pipeline tests.

Doctrine (SURVEY §4a): the native path is always compared against the
pure-python reference implementation in the same process.
"""

import os

import numpy as np
import pytest

from apex_tpu import _native
from apex_tpu.data import (DataLoader, f32_to_bf16, flatten, native_available,
                           transform_batch, unflatten)
from apex_tpu.data.loader import _transform_batch_py


def test_native_builds():
    """g++ is in the image; the native lib must actually build here."""
    assert native_available(), "native lib failed to build"
    assert _native.lib().atp_version() == 1


def test_flatten_unflatten_roundtrip():
    rs = np.random.RandomState(0)
    arrays = [rs.randn(7, 3).astype(np.float32),
              rs.randint(0, 255, (4, 2, 2), dtype=np.uint8),
              rs.randn(11).astype(np.float64)]
    flat = flatten(arrays)
    assert flat.nbytes == sum(a.nbytes for a in arrays)
    outs = unflatten(flat, arrays)
    for a, o in zip(arrays, outs):
        assert o.dtype == a.dtype and o.shape == a.shape
        np.testing.assert_array_equal(a, o)


def test_flatten_matches_python_fallback():
    rs = np.random.RandomState(1)
    arrays = [rs.randn(5, 5).astype(np.float32) for _ in range(3)]
    flat_native = flatten(arrays)
    ref = np.concatenate([a.view(np.uint8).reshape(-1) for a in arrays])
    np.testing.assert_array_equal(flat_native, ref)


def test_f32_to_bf16_rne():
    import ml_dtypes
    rs = np.random.RandomState(2)
    x = np.concatenate([rs.randn(1000).astype(np.float32),
                        [0.0, -0.0, np.inf, -np.inf, np.nan, 1e38, -1e-38]])
    got = f32_to_bf16(x)
    ref = x.astype(ml_dtypes.bfloat16).view(np.uint16)
    # NaNs may differ in payload; compare non-nan bitwise, nan as nan
    nan = np.isnan(x)
    np.testing.assert_array_equal(got[~nan], ref[~nan])
    assert np.isnan(got[nan].view(ml_dtypes.bfloat16).astype(np.float32)).all()


def test_transform_batch_center_crop_matches_python():
    rs = np.random.RandomState(3)
    images = rs.randint(0, 256, (10, 12, 14, 3), dtype=np.uint8)
    idx = np.asarray([3, 1, 7], np.int64)
    mean, std = (0.5, 0.4, 0.3), (0.2, 0.25, 0.3)
    got = transform_batch(images, idx, 8, 8, mean, std, augment=False)
    ref = _transform_batch_py(images, idx, 8, 8,
                              np.asarray(mean, np.float32),
                              np.asarray(std, np.float32), False, False, 0)
    assert got.dtype == np.float32 and got.shape == (3, 8, 8, 3)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_transform_batch_bf16_output():
    import ml_dtypes
    rs = np.random.RandomState(4)
    images = rs.randint(0, 256, (4, 8, 8, 3), dtype=np.uint8)
    idx = np.arange(4, dtype=np.int64)
    f32 = transform_batch(images, idx, 8, 8, (0.5,) * 3, (0.25,) * 3)
    b16 = transform_batch(images, idx, 8, 8, (0.5,) * 3, (0.25,) * 3,
                          out_bf16=True)
    back = b16.view(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_allclose(back, f32, rtol=1e-2, atol=1e-2)


def test_transform_batch_augment_in_bounds():
    rs = np.random.RandomState(5)
    images = rs.randint(0, 256, (6, 16, 16, 3), dtype=np.uint8)
    idx = np.arange(6, dtype=np.int64)
    out = transform_batch(images, idx, 8, 8, (0.0,) * 3, (1.0,) * 3,
                          augment=True, seed=7)
    # normalized values must lie in [0, 1] given mean 0 / std 1
    assert out.min() >= 0.0 and out.max() <= 1.0
    # different seeds give different crops (statistically certain)
    out2 = transform_batch(images, idx, 8, 8, (0.0,) * 3, (1.0,) * 3,
                           augment=True, seed=8)
    assert not np.allclose(out, out2)


@pytest.mark.parametrize("workers", [1, 3])
def test_dataloader_label_image_correspondence(workers):
    """Batches must come back in submit order: encode each image's index in
    its pixels and check it matches the label, across multiple workers."""
    n = 32
    images = np.zeros((n, 4, 4, 1), np.uint8)
    for i in range(n):
        images[i] = i
    labels = np.arange(n, dtype=np.int32)
    dl = DataLoader(images, labels, batch_size=4, mean=(0.0,), std=(1.0,),
                    augment=False, shuffle=True, seed=3, prefetch=3,
                    workers=workers)
    seen = []
    for x, y in dl:
        # pixel value / 255 == index / 255  =>  recover index
        rec = np.round(x[:, 0, 0, 0] * 255.0).astype(np.int32)
        np.testing.assert_array_equal(rec, y)
        seen.extend(y.tolist())
    assert sorted(seen) == list(range(n))


def test_dataloader_epochs_reshuffle():
    n = 16
    images = np.zeros((n, 2, 2, 1), np.uint8)
    labels = np.arange(n, dtype=np.int32)
    dl = DataLoader(images, labels, batch_size=4, mean=(0.0,), std=(1.0,),
                    augment=False, shuffle=True, seed=0)
    e1 = [y for _, ys in dl for y in ys]
    e2 = [y for _, ys in dl for y in ys]
    assert sorted(e1) == sorted(e2) == list(range(n))
    assert e1 != e2  # different epoch permutation


def test_dataloader_python_fallback_parity(monkeypatch):
    """Force the numpy path and check it yields the same stream."""
    n = 12
    rs = np.random.RandomState(6)
    images = rs.randint(0, 256, (n, 6, 6, 2), dtype=np.uint8)
    labels = np.arange(n, dtype=np.int32)
    kw = dict(batch_size=3, crop=(4, 4), mean=(0.5, 0.5), std=(0.3, 0.3),
              augment=False, shuffle=True, seed=1)
    native = list(DataLoader(images, labels, **kw))
    monkeypatch.setattr(_native, "lib", lambda: None)
    fallback = list(DataLoader(images, labels, **kw))
    assert len(native) == len(fallback) == 4
    for (xn, yn), (xp, yp) in zip(native, fallback):
        np.testing.assert_array_equal(yn, yp)
        np.testing.assert_allclose(xn, xp, rtol=1e-6, atol=1e-6)


def test_transform_batch_validates_bounds():
    """Oversize crops / out-of-range indices must raise on both
    the native and numpy paths (the C ABI would read out of bounds)."""
    images = np.zeros((4, 8, 8, 3), np.uint8)
    idx = np.arange(2)
    with pytest.raises(ValueError, match="crop"):
        transform_batch(images, idx, 16, 8, (0.5,) * 3, (0.2,) * 3)
    with pytest.raises(ValueError, match="crop"):
        transform_batch(images, idx, 8, 9, (0.5,) * 3, (0.2,) * 3)
    with pytest.raises(ValueError, match="indices"):
        transform_batch(images, np.array([0, 4]), 4, 4, (0.5,) * 3, (0.2,) * 3)
    with pytest.raises(ValueError, match="indices"):
        transform_batch(images, np.array([-1]), 4, 4, (0.5,) * 3, (0.2,) * 3)


def test_dataloader_validates_crop_and_small_dataset():
    images = np.zeros((3, 8, 8, 3), np.uint8)
    labels = np.zeros(3, np.int64)
    with pytest.raises(ValueError, match="crop"):
        DataLoader(images, labels, batch_size=2, crop=(9, 8))
    with pytest.raises(ValueError, match="zero batches"):
        DataLoader(images, labels, batch_size=8, drop_last=True)
    # drop_last=False with a small dataset yields the ragged batch
    dl = DataLoader(images, labels, batch_size=8, drop_last=False,
                    augment=False, shuffle=False)
    batches = list(dl)
    assert len(batches) == 1 and len(batches[0][0]) == 3


def test_build_output_is_keyed_on_source_content_not_mtime(tmp_path,
                                                           monkeypatch):
    """A binary is reused only if built from exactly this source with
    exactly these flags: the name carries their hash, so touching the
    mtime changes nothing and editing a byte names a new file."""
    src = tmp_path / "native.cpp"
    src.write_text("extern \"C\" int atp_version() { return 1; }\n")
    monkeypatch.setattr(_native, "_SRC", str(src))
    a = _native._so_path()
    os.utime(src, (1, 1))
    assert _native._so_path() == a
    src.write_text("extern \"C\" int atp_version() { return 2; }\n")
    b = _native._so_path()
    assert b != a
    monkeypatch.setattr(_native, "_FLAGS", _native._FLAGS + ("-g",))
    assert _native._so_path() not in (a, b)
    assert "-march=native" not in _native._FLAGS


def test_failed_build_warns_with_compiler_stderr(tmp_path, monkeypatch):
    """A build that was attempted and failed is reported, not swallowed;
    the caller still gets None and takes the numpy path."""
    src = tmp_path / "broken.cpp"
    src.write_text("this is not C++\n")
    monkeypatch.setattr(_native, "_SRC", str(src))
    monkeypatch.setattr(_native, "_BUILD_DIR", str(tmp_path / "build"))
    with pytest.warns(RuntimeWarning, match="(?s)native build failed.*error"):
        assert _native._build() is None
    assert not list((tmp_path / "build").glob("*.so"))
