"""Data layer tests (reference capability: src/util.py:21-106 +
src/data_loader_ops/my_data_loader.py)."""

import numpy as np
import pytest

from pytorch_distributed_nn_tpu.data import DataLoader, augment_batch, load_dataset


@pytest.mark.parametrize(
    "name,shape,classes",
    [
        ("MNIST", (28, 28, 1), 10),
        ("Cifar10", (32, 32, 3), 10),
        ("Cifar100", (32, 32, 3), 100),
        ("SVHN", (32, 32, 3), 10),
    ],
)
def test_load_dataset_shapes(name, shape, classes):
    ds = load_dataset(name, train=True, synthetic_size=256)
    assert ds.images.shape == (256, *shape)
    assert ds.images.dtype == np.float32
    assert ds.labels.min() >= 0 and ds.labels.max() < classes
    assert ds.num_classes == classes
    assert ds.synthetic


def test_unknown_dataset_raises():
    with pytest.raises(ValueError):
        load_dataset("ImageNet21k", train=True, synthetic_size=8)


def test_normalization_is_applied():
    ds = load_dataset("Cifar10", train=False, synthetic_size=512)
    # normalized data should be roughly zero-centered, not in [0,1]
    assert abs(float(ds.images.mean())) < 2.0
    assert float(ds.images.std()) > 0.3


def test_augment_batch_preserves_shape_and_changes_pixels():
    rng = np.random.RandomState(0)
    x = rng.randn(8, 32, 32, 3).astype(np.float32)
    out = augment_batch(x, np.random.RandomState(1))
    assert out.shape == x.shape
    assert not np.allclose(out, x)


def test_augment_batch_matches_per_image_loop():
    """The vectorized gather must agree with the obvious per-image loop
    (same rng consumption order: ys, xs, flips)."""
    rng = np.random.RandomState(7)
    x = rng.randn(16, 32, 32, 3).astype(np.float32)
    out = augment_batch(x, np.random.RandomState(3))

    ref_rng = np.random.RandomState(3)
    n, h, w, _ = x.shape
    padded = np.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)), mode="reflect")
    ys = ref_rng.randint(0, 9, size=n)
    xs = ref_rng.randint(0, 9, size=n)
    flip = ref_rng.rand(n) < 0.5
    want = np.empty_like(x)
    for i in range(n):
        crop = padded[i, ys[i]:ys[i] + h, xs[i]:xs[i] + w]
        want[i] = crop[:, ::-1] if flip[i] else crop
    np.testing.assert_array_equal(out, want)


def test_native_augment_matches_numpy_bitwise():
    """The C++ engine and the numpy gather are both pure index movement:
    identical bytes for identical draws."""
    from pytorch_distributed_nn_tpu.data import native_augment
    from pytorch_distributed_nn_tpu.data.datasets import _augment_numpy

    if not native_augment.available():
        pytest.skip("native augment library unavailable (no toolchain)")
    rng = np.random.RandomState(5)
    x = rng.randn(32, 32, 32, 3).astype(np.float32)
    ys = rng.randint(0, 9, size=32)
    xs = rng.randint(0, 9, size=32)
    flip = rng.rand(32) < 0.5
    got = native_augment.augment_f32(x, ys, xs, flip)
    want = _augment_numpy(x, ys, xs, flip)
    np.testing.assert_array_equal(got, want)


def test_prepare_data_graceful_offline(tmp_path):
    """On a zero-egress host prepare_data reports per-dataset failures
    instead of raising (reference parity: src/data/data_prepare.py would
    crash; the capability here is a clean offline story)."""
    from pytorch_distributed_nn_tpu.data.datasets import prepare_data

    results = prepare_data(str(tmp_path), ("MNIST",))
    assert set(results) == {"MNIST"}
    assert results["MNIST"] == "ok" or results["MNIST"].startswith("failed")


def test_fetch_verifies_sha256(tmp_path, monkeypatch):
    """A mirror serving non-canonical bytes is rejected before extraction
    (ADVICE r2: integrity was parse-level only); matching bytes pass."""
    import hashlib
    import io

    from pytorch_distributed_nn_tpu.data import datasets as D

    payload = b"not the canonical archive"

    class _Resp(io.BytesIO):
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(
        "urllib.request.urlopen", lambda url, timeout=0.0: _Resp(payload)
    )
    dest = tmp_path / "cifar-10-python.tar.gz"  # has a pinned digest
    with pytest.raises(RuntimeError, match="checksum mismatch"):
        D._fetch("https://mirror.invalid/cifar-10-python.tar.gz", str(dest))
    assert not dest.exists()
    assert not (tmp_path / "cifar-10-python.tar.gz.part").exists()

    monkeypatch.setitem(
        D._SHA256, "ok.bin", hashlib.sha256(payload).hexdigest()
    )
    D._fetch("https://mirror.invalid/ok.bin", str(tmp_path / "ok.bin"))
    assert (tmp_path / "ok.bin").read_bytes() == payload


def _write_idx(path, arr):
    import numpy as np

    ndim = arr.ndim
    magic = (0x08 << 8) | ndim  # 0x08 = ubyte type code
    with open(path, "wb") as f:
        f.write(magic.to_bytes(4, "big"))
        for d in arr.shape:
            f.write(int(d).to_bytes(4, "big"))
        f.write(arr.astype(np.uint8).tobytes())


def test_native_mnist_idx_parser(tmp_path):
    """The real-data read path, exercised offline: write canonical-format
    MNIST idx files and load them without torch/torchvision."""
    rng = np.random.RandomState(0)
    raw = tmp_path / "mnist_data" / "MNIST" / "raw"
    raw.mkdir(parents=True)
    for stem, n in (("train", 64), ("t10k", 32)):
        _write_idx(raw / f"{stem}-images-idx3-ubyte",
                   rng.randint(0, 256, (n, 28, 28)))
        _write_idx(raw / f"{stem}-labels-idx1-ubyte",
                   rng.randint(0, 10, (n,)))
    ds = load_dataset("MNIST", train=True, data_dir=str(tmp_path))
    assert not ds.synthetic
    assert ds.images.shape == (64, 28, 28, 1)
    ds = load_dataset("MNIST", train=False, data_dir=str(tmp_path))
    assert not ds.synthetic and len(ds) == 32


def test_native_cifar_pickle_parser(tmp_path):
    """CIFAR-10 batch pickles parse without torchvision."""
    import pickle

    rng = np.random.RandomState(1)
    root = tmp_path / "cifar10_data" / "cifar-10-batches-py"
    root.mkdir(parents=True)
    for fname, n in [(f"data_batch_{i}", 20) for i in range(1, 6)] + [
        ("test_batch", 30)
    ]:
        with open(root / fname, "wb") as f:
            pickle.dump(
                {b"data": rng.randint(0, 256, (n, 3072), dtype=np.uint8),
                 b"labels": rng.randint(0, 10, (n,)).tolist()},
                f,
            )
    ds = load_dataset("Cifar10", train=True, data_dir=str(tmp_path))
    assert not ds.synthetic
    assert ds.images.shape == (100, 32, 32, 3)  # 5 x 20 concatenated
    ds = load_dataset("Cifar10", train=False, data_dir=str(tmp_path))
    assert len(ds) == 30


def test_native_svhn_mat_parser(tmp_path):
    """SVHN .mat parses via scipy; class '10' remaps to digit 0."""
    savemat = pytest.importorskip("scipy.io").savemat

    rng = np.random.RandomState(2)
    root = tmp_path / "svhn_data"
    root.mkdir()
    for split, n in (("train", 24), ("test", 12)):
        savemat(root / f"{split}_32x32.mat", {
            "X": rng.randint(0, 256, (32, 32, 3, n), dtype=np.uint8),
            "y": rng.randint(1, 11, (n, 1)),
        })
    ds = load_dataset("SVHN", train=True, data_dir=str(tmp_path))
    assert not ds.synthetic
    assert ds.images.shape == (24, 32, 32, 3)
    assert ds.labels.min() >= 0 and ds.labels.max() <= 9


def test_real_data_when_present(tmp_path):
    """Exercises the torchvision on-disk read path with a real-format MNIST
    tree when available; skips cleanly on zero-egress hosts."""
    from pytorch_distributed_nn_tpu.data.datasets import prepare_data

    results = prepare_data(str(tmp_path), ("MNIST",))
    if results["MNIST"].startswith("failed"):
        pytest.skip(f"no network egress: {results['MNIST']}")
    ds = load_dataset("MNIST", train=False, data_dir=str(tmp_path))
    assert not ds.synthetic
    assert ds.images.shape == (10000, 28, 28, 1)


def test_loader_next_batch_wraps_epochs():
    ds = load_dataset("MNIST", train=True, synthetic_size=64)
    loader = DataLoader(ds, batch_size=32, seed=0, prefetch=0)
    seen = [loader.next_batch() for _ in range(5)]  # 2.5 epochs
    for x, y in seen:
        assert x.shape == (32, 28, 28, 1)
        assert y.shape == (32,)


def test_loader_prefetch_thread():
    ds = load_dataset("MNIST", train=True, synthetic_size=64)
    loader = DataLoader(ds, batch_size=16, prefetch=2)
    try:
        for _ in range(6):
            x, y = loader.next_batch()
            assert x.shape == (16, 28, 28, 1)
    finally:
        loader.close()


def _take(loader, n):
    try:
        return [loader.next_batch() for _ in range(n)]
    finally:
        loader.close()


@pytest.mark.parametrize(
    "name,batch,n,kw_a,kw_b,same",
    [
        # unaugmented: byte-identical to the in-process path, across epoch
        # wrap-around (7 batches > 2 epochs of 3)
        ("MNIST", 32, 7, dict(shuffle=False, workers=2),
         dict(shuffle=False, prefetch=0), True),
        # augmented + shuffled: two loaders with one seed give one stream
        ("Cifar10", 64, 3, dict(seed=3, workers=2), dict(seed=3, workers=2),
         True),
        # the loader seed reaches the workers' augment stream: a different
        # --seed draws different crops/flips (and a different shuffle)
        ("Cifar10", 64, 3, dict(seed=3, workers=2), dict(seed=4, workers=2),
         False),
    ],
    ids=["equals_sync_path", "one_seed_one_stream", "other_seed_other_stream"],
)
def test_loader_workers(name, batch, n, kw_a, kw_b, same):
    """workers=N (the reference's fork-worker loader capability,
    my_data_loader.py:37-53): worker threads gather, normalize and augment
    from the uint8 pixels, per-batch seeded by (loader seed, batch
    counter). Each case builds its own loaders."""
    ds = load_dataset(name, train=True, synthetic_size=96)
    assert ds.augment == (name != "MNIST")
    got_a = _take(DataLoader(ds, batch_size=batch, **kw_a), n)
    got_b = _take(DataLoader(ds, batch_size=batch, **kw_b), n)
    assert got_a[0][0].shape == (batch, *ds.raw_images.shape[1:])
    assert got_a[0][0].dtype == np.float32
    if not same:
        assert not np.array_equal(got_a[0][0], got_b[0][0])
        return
    for (xa, ya), (xb, yb) in zip(got_a, got_b):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def test_loader_close_with_batches_in_flight_returns():
    """close() while the batches submitted ahead are still being made
    returns promptly, every time, and leaves no worker behind. (Before
    PR 25 the workers were processes and close() terminated them with
    results in flight: on a loaded host one died holding the result
    pipe's lock and close() never returned.)"""
    import multiprocessing
    import threading
    import time

    cds = load_dataset("Cifar10", train=True, synthetic_size=256)
    for seed in range(20):
        loader = DataLoader(cds, batch_size=64, seed=seed, workers=2, prefetch=4)
        loader.next_batch()  # leaves prefetch - 1 batches submitted
        assert len(loader._pending) == 3
        t0 = time.monotonic()
        loader.close()
        assert time.monotonic() - t0 < 5.0
        assert not loader._pending
    assert multiprocessing.active_children() == []
    assert not [
        t.name for t in threading.enumerate()
        if t.name.startswith("pdtn-loader-worker")
    ]


def test_loader_worker_exception_reaches_next_batch(monkeypatch):
    """A worker thread that raises delivers its exception to the caller of
    next_batch() (Future.result), not a wait."""
    from pytorch_distributed_nn_tpu.data import loader as loader_mod

    def boom(images, rng):
        raise ValueError("augment failed")

    monkeypatch.setattr(loader_mod, "augment_batch", boom)
    cds = load_dataset("Cifar10", train=True, synthetic_size=128)
    loader = DataLoader(cds, batch_size=64, workers=2)
    try:
        with pytest.raises(ValueError, match="augment failed"):
            loader.next_batch()
    finally:
        loader.close()


def test_loader_epoch_batches_covers_dataset():
    ds = load_dataset("MNIST", train=False, synthetic_size=50)
    loader = DataLoader(ds, batch_size=10, shuffle=False, prefetch=0)
    batches = list(loader.epoch_batches())
    assert len(batches) == 5
    all_y = np.concatenate([y for _, y in batches])
    np.testing.assert_array_equal(all_y, ds.labels)


def test_loader_rejects_oversized_batch():
    ds = load_dataset("MNIST", train=False, synthetic_size=8)
    with pytest.raises(ValueError):
        DataLoader(ds, batch_size=16)


def _mesh():
    from pytorch_distributed_nn_tpu.parallel import make_mesh

    return make_mesh()


def test_device_loader_matches_host_normalization():
    """Without augmentation, the on-device (uint8 -> normalize) path must
    reproduce the host loader's f32 pixels exactly (same constants)."""
    from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader

    ds = load_dataset("MNIST", train=False, synthetic_size=64)
    mesh = _mesh()
    dev = DeviceDataLoader(ds, 32, mesh, shuffle=False)
    host = DataLoader(ds, 32, shuffle=False, prefetch=0)
    for (xd, yd), (xh, yh) in zip(dev.epoch_batches(), host.epoch_batches()):
        np.testing.assert_allclose(np.asarray(xd), xh, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(yd), yh)


def test_device_loader_augments_on_device():
    """Augmented batches stay shape-correct, differ from the originals, and
    stay within the padded-crop value range (crop/flip only move pixels)."""
    from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader

    ds = load_dataset("Cifar10", train=True, synthetic_size=128)
    assert ds.augment
    loader = DeviceDataLoader(ds, 64, _mesh(), shuffle=False, seed=3)
    x, y = loader.next_batch()
    assert x.shape == (64, 32, 32, 3) and y.shape == (64,)
    raw_sorted = np.sort(ds.images[:64].ravel())
    # crops/flips permute pixels (plus reflect-padding duplicates); the
    # value SET stays inside the original normalized range
    assert float(np.asarray(x).min()) >= raw_sorted[0] - 1e-4
    assert float(np.asarray(x).max()) <= raw_sorted[-1] + 1e-4
    x2, _ = loader.next_batch()
    assert not np.allclose(np.asarray(x), np.asarray(x2))


def test_device_loader_epochs_and_sharding():
    from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader

    ds = load_dataset("MNIST", train=True, synthetic_size=64)
    mesh = _mesh()
    loader = DeviceDataLoader(ds, 32, mesh, shuffle=True, seed=0)
    assert loader.steps_per_epoch == 2
    for _ in range(5):  # 2.5 epochs, wraps cleanly
        x, y = loader.next_batch()
        assert x.shape == (32, 28, 28, 1)
    # output is sharded over the mesh's data axis
    assert "data" in str(x.sharding.spec)


# --- DeviceDataLoader.prep: bit identity with the prep it replaced ---------


def _parent_prep(dataset, draws=None):
    """The plain reference: ``DeviceDataLoader.prep`` as it stood before it
    selected by index (PR 29's parent, loader.py:305-330), frozen here. It
    gathers float32 pixels, reflect-pads the batch, crops with two
    ``take_along_axis`` gathers and flips with a ``where`` over the
    reversed copy. ``draws(idx, key) -> (dy, dx, flip)`` stands in for its
    three PRNG draws where a test has to force them."""
    import jax
    import jax.numpy as jnp

    mean = jnp.asarray(dataset.mean, jnp.float32) * 255.0
    std = jnp.asarray(dataset.std, jnp.float32) * 255.0
    augment = dataset.augment
    H, W = dataset.raw_images.shape[1:3]

    def prep(images, labels, idx, key):
        x = images[idx].astype(jnp.float32)  # (B,H,W,C) device gather
        y = labels[idx]
        if augment:
            kc1, kc2, kf = jax.random.split(key, 3)
            padded = jnp.pad(
                x, ((0, 0), (4, 4), (4, 4), (0, 0)), mode="reflect"
            )
            dy = jax.random.randint(kc1, (idx.shape[0],), 0, 9)
            dx = jax.random.randint(kc2, (idx.shape[0],), 0, 9)
            flip = jax.random.bernoulli(kf, 0.5, (idx.shape[0],))
            if draws is not None:
                dy, dx, flip = draws(idx, key)
            ii = dy[:, None] + jnp.arange(H)  # (B, H)
            jj = dx[:, None] + jnp.arange(W)  # (B, W)
            x = jnp.take_along_axis(
                padded, ii[:, :, None, None], axis=1
            )  # (B, H, W+8, C)
            x = jnp.take_along_axis(
                x, jj[:, None, :, None], axis=2
            )  # (B, H, W, C)
            x = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
        x = (x - mean) / std
        return x, y

    return jax.jit(prep)


def _assert_prep_is_the_parents(ds, batch, mesh, draws=None, seeds=(0, 11)):
    import jax

    from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader

    loader = DeviceDataLoader(ds, batch, mesh, shuffle=True, seed=5)
    reference = _parent_prep(ds, draws)
    labels = ds.labels.astype(np.int32)
    rng = np.random.RandomState(batch)
    for seed in seeds:
        idx = rng.randint(0, len(ds), size=batch)
        idx[-1] = idx[0]  # an index that repeats
        loader._key = jax.random.PRNGKey(seed)
        idx_dev, key = loader._idx_key(idx)
        x, y = loader._prep(loader.images, loader.labels, idx_dev, key)
        xr, yr = reference(ds.raw_images, labels, idx.astype(np.int32), key)
        assert x.dtype == np.float32 and x.shape == xr.shape
        assert np.array_equal(np.asarray(x), np.asarray(xr))
        assert np.array_equal(np.asarray(y), np.asarray(yr))
    return loader, x


@pytest.mark.parametrize("batch", [1, 7, 64])
@pytest.mark.parametrize("name", ["Cifar10", "Cifar100", "SVHN", "MNIST"])
def test_device_prep_bit_identical_to_parent(name, batch):
    from pytorch_distributed_nn_tpu.parallel import make_mesh

    ds = load_dataset(name, train=True, synthetic_size=96)
    assert ds.augment == (name != "MNIST")
    _assert_prep_is_the_parents(ds, batch, make_mesh(1))


@pytest.mark.parametrize("name", ["Cifar10", "Cifar100", "SVHN", "MNIST"])
def test_device_prep_bit_identical_to_parent_sharded_idx(name):
    """Two devices on the data axis: idx sharded, the data set replicated,
    the batch sharded as it comes out."""
    from pytorch_distributed_nn_tpu.parallel import make_mesh

    ds = load_dataset(name, train=True, synthetic_size=96)
    _, x = _assert_prep_is_the_parents(ds, 64, make_mesh(2))
    assert "data" in str(x.sharding.spec)
    assert len(x.sharding.device_set) == 2


def test_device_prep_bit_identical_at_every_corner(monkeypatch):
    """Crops at every corner, edge and the centre of the padded image
    (dy, dx in {0, 4, 8}), each flipped and not: the reflect pad and the
    flip are folded into the source indices, and this is where a fold that
    is off by one shows."""
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.data import loader as loader_mod
    from pytorch_distributed_nn_tpu.parallel import make_mesh

    corners = [(dy, dx, f) for dy in (0, 4, 8) for dx in (0, 4, 8)
               for f in (False, True)]
    dy, dx, flip = (jnp.asarray(c) for c in zip(*corners))
    monkeypatch.setattr(
        loader_mod, "crop_flip_draws", lambda key, batch: (dy, dx, flip))
    ds = load_dataset("Cifar10", train=True, synthetic_size=96)
    _assert_prep_is_the_parents(
        ds, len(corners), make_mesh(1), draws=lambda idx, key: (dy, dx, flip))


def test_device_prep_is_the_host_transform_pixel_for_pixel():
    """The loader's own draws fed to the host path's numpy transform give
    the loader's batch exactly: one transform, two implementations."""
    import jax

    from pytorch_distributed_nn_tpu.data.datasets import _augment_numpy
    from pytorch_distributed_nn_tpu.data.loader import (
        DeviceDataLoader,
        crop_flip_draws,
    )
    from pytorch_distributed_nn_tpu.parallel import make_mesh

    ds = load_dataset("SVHN", train=True, synthetic_size=96)
    loader = DeviceDataLoader(ds, 32, make_mesh(1), seed=2)
    idx = loader._next_idx()
    idx_dev, key = loader._idx_key(idx)
    x, _ = loader._prep(loader.images, loader.labels, idx_dev, key)
    dy, dx, flip = (np.asarray(d) for d in crop_flip_draws(key, len(idx)))
    assert len({*dy}) > 1 and len({*dx}) > 1 and 0 < flip.sum() < len(idx)
    moved = _augment_numpy(
        ds.raw_images[idx].astype(np.float32), dy, dx, flip)
    # the same float32 normalisation, compiled as the loader's is (XLA
    # folds a division by a constant; numpy's differs in the last bit)
    mean = np.asarray(ds.mean, np.float32) * np.float32(255.0)
    std = np.asarray(ds.std, np.float32) * np.float32(255.0)
    normalise = jax.jit(lambda m: (m - mean) / std)
    assert np.array_equal(np.asarray(x), np.asarray(normalise(moved)))


def test_device_prep_fn_takes_the_nhwc_data_set_too():
    """``.images`` holds flat rows; ``prep_fn`` also lowers from the
    (N, H, W, C) uint8 array (benchmark/compile_for_chip.py hands it one)."""
    import jax

    from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader
    from pytorch_distributed_nn_tpu.parallel import make_mesh

    ds = load_dataset("Cifar10", train=True, synthetic_size=96)
    loader = DeviceDataLoader(ds, 16, make_mesh(1), seed=1)
    assert loader.images.shape == (96, 32 * 32 * 3)
    assert loader.images.dtype == np.uint8
    idx_dev, key = loader._idx_key(loader._next_idx())
    prep = jax.jit(loader.prep_fn)
    flat = prep(loader.images, loader.labels, idx_dev, key)
    nhwc = prep(ds.raw_images, loader.labels, idx_dev, key)
    assert np.array_equal(np.asarray(flat[0]), np.asarray(nhwc[0]))
    assert np.array_equal(np.asarray(flat[1]), np.asarray(nhwc[1]))
