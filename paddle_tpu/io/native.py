"""ctypes binding for the native token data loader (csrc/dataloader.cpp).

Parity: the reference's native reader/worker pipeline — this keeps token
batch materialization (mmap reads + shuffle + copy) off the Python
interpreter; Python only pops finished int32 buffers and device_puts.

Builds the .so on first use (g++ is in the image) and again whenever its
source or the Makefile is newer — ``make`` decides, so a stale library
from another checkout or compiler is never loaded as found. Callers
should catch ImportError/OSError and use the pure-python DataLoader.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Iterator, Optional, Tuple

import numpy as np

_CSRC = os.path.join(os.path.dirname(__file__), "..", "..", "csrc")
_SO = os.path.join(_CSRC, "libptdataloader.so")
_lib = None


def _build(so_path: str) -> str:
    """``make`` the library: a no-op when it is newer than its source
    and the Makefile, a rebuild when it is missing or stale."""
    subprocess.run(
        ["make", "-C", _CSRC, os.path.basename(so_path)],
        check=True, capture_output=True,
    )
    return so_path


def _load():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(_build(_SO))
    lib.ptdl_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int64]
    lib.ptdl_open.restype = ctypes.c_int
    lib.ptdl_num_seqs.argtypes = [ctypes.c_int]
    lib.ptdl_num_seqs.restype = ctypes.c_int64
    lib.ptdl_start_epoch.argtypes = [
        ctypes.c_int, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
    ]
    lib.ptdl_start_epoch.restype = ctypes.c_int
    lib.ptdl_next_batch.argtypes = [
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.ptdl_next_batch.restype = ctypes.c_int64
    lib.ptdl_close.argtypes = [ctypes.c_int]
    lib.ptdl_close.restype = ctypes.c_int
    _lib = lib
    return lib


class TokenBinDataset:
    """Fixed-length sequences from a binary token shard (uint16/uint32)."""

    def __init__(self, path: str, seq_len: int, token_bytes: int = 2):
        lib = _load()
        self._lib = lib
        self.seq_len = seq_len
        self.handle = lib.ptdl_open(
            path.encode(), token_bytes, seq_len
        )
        if self.handle < 0:
            raise OSError(
                f"ptdl_open({path!r}) failed with code {self.handle}"
            )
        self.num_seqs = lib.ptdl_num_seqs(self.handle)

    def __len__(self):
        return self.num_seqs

    def batches(
        self,
        batch_size: int,
        seed: int = 0,
        shuffle: bool = True,
        drop_last: bool = True,
        num_threads: int = 2,
        return_indices: bool = False,
    ) -> Iterator[np.ndarray]:
        lib = self._lib
        rc = lib.ptdl_start_epoch(
            self.handle, seed, batch_size, int(drop_last), int(shuffle),
            num_threads,
        )
        if rc != 0:
            raise OSError(f"ptdl_start_epoch failed: {rc}")
        buf = np.empty((batch_size, self.seq_len), np.int32)
        idx = np.empty((batch_size,), np.int64)
        while True:
            n = lib.ptdl_next_batch(
                self.handle,
                buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            )
            if n <= 0:
                return
            batch = buf[:n].copy()
            if return_indices:
                yield batch, idx[:n].copy()
            else:
                yield batch

    def close(self):
        if self.handle >= 0:
            self._lib.ptdl_close(self.handle)
            self.handle = -1

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


_ckpt_lib = None
_CKPT_SO = os.path.join(_CSRC, "libptckpt.so")


def load_ckpt_writer():
    """ctypes handle for the native parallel checkpoint chunk writer
    (csrc/ckptio.cpp). Builds on first use; raises on failure — callers
    fall back to the pure-python np.save loop. Build failure is cached
    so periodic saves don't re-spawn a doomed make each time."""
    global _ckpt_lib
    if _ckpt_lib is False:
        raise OSError("native checkpoint writer unavailable (cached)")
    if _ckpt_lib is not None:
        return _ckpt_lib
    try:
        _build(_CKPT_SO)
    except (OSError, subprocess.CalledProcessError):
        _ckpt_lib = False
        raise
    lib = ctypes.CDLL(_CKPT_SO)
    lib.ptck_write_batch.argtypes = [
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.ptck_write_batch.restype = ctypes.c_int
    _ckpt_lib = lib
    return lib
