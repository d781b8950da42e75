"""Native C++ token loader: build, read-back correctness, shuffle
determinism, epoch exhaustion (parity model: reader op unit tests)."""

import numpy as np
import pytest

pytest.importorskip("ctypes")


@pytest.fixture(scope="module")
def token_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("data") / "tokens.bin"
    # 32 sequences of length 16, tokens = seq_index*100 + position (uint16)
    arr = np.zeros((32, 16), np.uint16)
    for i in range(32):
        arr[i] = i * 100 + np.arange(16)
    arr.tofile(path)
    return str(path)


def test_build_and_read(token_file):
    from paddle_tpu.io.native import TokenBinDataset

    ds = TokenBinDataset(token_file, seq_len=16)
    assert len(ds) == 32
    batches = list(ds.batches(batch_size=8, shuffle=False, seed=0))
    assert len(batches) == 4
    np.testing.assert_array_equal(
        batches[0][0], np.arange(16)
    )
    np.testing.assert_array_equal(
        batches[3][7], 3100 + np.arange(16)
    )
    ds.close()


def test_shuffle_deterministic_and_complete(token_file):
    from paddle_tpu.io.native import TokenBinDataset

    ds = TokenBinDataset(token_file, seq_len=16)
    a = np.concatenate(
        [b[:, 0] for b in ds.batches(8, seed=7, shuffle=True)]
    )
    b = np.concatenate(
        [b[:, 0] for b in ds.batches(8, seed=7, shuffle=True)]
    )
    c = np.concatenate(
        [b[:, 0] for b in ds.batches(8, seed=8, shuffle=True)]
    )
    np.testing.assert_array_equal(a, b)  # same seed → same order
    assert not np.array_equal(a, c)  # different seed → different order
    assert sorted(a.tolist()) == sorted((np.arange(32) * 100).tolist())
    ds.close()


def test_drop_last_false(token_file):
    from paddle_tpu.io.native import TokenBinDataset

    ds = TokenBinDataset(token_file, seq_len=16)
    batches = list(ds.batches(batch_size=5, shuffle=False, drop_last=False))
    assert [len(b) for b in batches] == [5, 5, 5, 5, 5, 5, 2]
    ds.close()


def test_native_ckpt_writer_batch(tmp_path):
    """The C thread-pool chunk writer must produce byte-valid .npy files
    np.load can read back (incl. bf16-as-uint16 payloads)."""
    from paddle_tpu.distributed.checkpoint import _native_write_chunks

    rng = np.random.default_rng(0)
    files = []
    refs = []
    for i in range(10):
        a = rng.standard_normal((32, 17)).astype(np.float32)
        files.append((str(tmp_path / f"chunk_{i}.npy"), a))
        refs.append(a)
    u16 = (rng.integers(0, 2**16, (8, 8))).astype(np.uint16)
    files.append((str(tmp_path / "bits.npy"), u16))
    assert _native_write_chunks(files) is True
    for (path, _), ref in zip(files[:-1], refs):
        np.testing.assert_array_equal(np.load(path), ref)
    np.testing.assert_array_equal(np.load(str(tmp_path / "bits.npy")), u16)


def test_ckpt_writer_python_fallback(tmp_path, monkeypatch):
    """With the native library unavailable, saves still succeed via the
    np.save loop."""
    from paddle_tpu.distributed import checkpoint as ckpt

    monkeypatch.setattr(ckpt, "_native_write_chunks", lambda files: False)
    import jax.numpy as jnp

    ckpt.save_state_dict({"w": jnp.ones((4, 4))}, str(tmp_path / "fb"))
    loaded = ckpt.load_state_dict(str(tmp_path / "fb"))
    np.testing.assert_allclose(np.asarray(loaded["w"]), 1.0)


def test_stale_library_is_rebuilt_not_loaded_as_found():
    """A library older than its source (another checkout's or another
    compiler's build left on disk) must be rebuilt before it is loaded:
    ``make`` decides, on every first use."""
    import os

    from paddle_tpu.io import native

    native._build(native._SO)
    src = os.path.join(native._CSRC, "dataloader.cpp")
    built = os.stat(native._SO).st_mtime_ns
    # up to date: a no-op, the file is left alone
    native._build(native._SO)
    assert os.stat(native._SO).st_mtime_ns == built
    # source newer than the library: rebuilt
    src_times = os.stat(src)
    try:
        os.utime(src, ns=(built + 10**9, built + 10**9))
        native._build(native._SO)
        assert os.stat(native._SO).st_mtime_ns > built
    finally:
        os.utime(src, ns=(src_times.st_atime_ns, src_times.st_mtime_ns))
