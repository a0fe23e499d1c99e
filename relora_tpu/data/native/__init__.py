"""Runtime build + ctypes bindings for the native index builders.

Parity with the reference's runtime ``make`` hook
(megatron_dataset/data_utils.py:470-482, Makefile): the shared object is
compiled on first use with g++ and cached next to the source, stamped with
the sha256 of the ``helpers.cpp`` it was built from — a copied checkout can
carry a stale or foreign ``.so`` whose mtime says nothing, so a library whose
stamp does not match the source is rebuilt.  If compilation fails (no compiler
on some hosts) callers fall back to the NumPy implementations.  Either way one
INFO line says which builder this process runs.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from relora_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "helpers.cpp")
_SO = os.path.join(_DIR, "_helpers.so")
_STAMP = _SO + ".sha256"  # digest of the helpers.cpp that _SO was built from
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _compile() -> bool:
    # build beside the target and rename: several processes (test workers)
    # may find the library stale at once, and a half-written .so must never
    # be what another one loads
    tmp = f"{_SO}.tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning(f"index builder: NumPy (native helpers build failed: {e})")
        return False


def load() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the helpers library; None if unavailable."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        stamp = ""
        if os.path.exists(_SO) and os.path.exists(_STAMP):
            with open(_STAMP) as f:
                stamp = f.read().strip()
        rebuilt = stamp != digest
        if rebuilt:
            if not _compile():
                return None
            with open(_STAMP, "w") as f:
                f.write(digest)
        logger.info(
            f"index builder: native ({'built now' if rebuilt else 'already built'} "
            f"from helpers.cpp sha256 {digest[:12]})"
        )
        lib = ctypes.CDLL(_SO)

        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")

        lib.relora_build_sample_idx_i32.argtypes = [
            i32p, i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64, i32p
        ]
        lib.relora_build_sample_idx_i32.restype = ctypes.c_int
        lib.relora_build_sample_idx_i64.argtypes = [
            i32p, i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64, i64p
        ]
        lib.relora_build_sample_idx_i64.restype = ctypes.c_int
        lib.relora_build_blending_indices.argtypes = [
            u8p, i64p, f64p, ctypes.c_int32, ctypes.c_int64
        ]
        lib.relora_build_blending_indices.restype = None
        lib.relora_shuffle_i64.argtypes = [i64p, ctypes.c_int64, ctypes.c_uint64]
        lib.relora_shuffle_i64.restype = None
        bert_args = [
            i64p, ctypes.c_int64, i32p, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_double, ctypes.c_uint32,
        ]
        lib.relora_count_bert_mapping.argtypes = list(bert_args)
        lib.relora_count_bert_mapping.restype = ctypes.c_int64
        lib.relora_fill_bert_mapping.argtypes = list(bert_args) + [i64p]
        lib.relora_fill_bert_mapping.restype = None
        blocks_args = [
            i64p, ctypes.c_int64, i32p, i32p, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32,
        ]
        lib.relora_count_blocks_mapping.argtypes = list(blocks_args)
        lib.relora_count_blocks_mapping.restype = ctypes.c_int64
        lib.relora_fill_blocks_mapping.argtypes = list(blocks_args) + [ctypes.c_uint32, i64p]
        lib.relora_fill_blocks_mapping.restype = None
        _LIB = lib
        return _LIB


def build_sample_idx_native(
    sizes: np.ndarray, doc_idx: np.ndarray, seq_length: int, num_samples: int
) -> Optional[np.ndarray]:
    """C++ sample-index packing; None if the native lib is unavailable.
    Uses int32 output when it fits (parity: dataset.py:189-203 dtype switch)."""
    lib = load()
    if lib is None:
        return None
    sizes = np.ascontiguousarray(sizes, dtype=np.int32)
    use_i32 = (
        len(doc_idx) <= np.iinfo(np.int32).max
        and int(sizes.max(initial=0)) <= np.iinfo(np.int32).max
    )
    if use_i32:
        doc = np.ascontiguousarray(doc_idx, dtype=np.int32)
        out = np.zeros((num_samples + 1, 2), dtype=np.int32)
        rc = lib.relora_build_sample_idx_i32(
            sizes, doc, len(doc), seq_length, num_samples, out.reshape(-1)
        )
    else:
        doc = np.ascontiguousarray(doc_idx, dtype=np.int64)
        out = np.zeros((num_samples + 1, 2), dtype=np.int64)
        rc = lib.relora_build_sample_idx_i64(
            sizes, doc, len(doc), seq_length, num_samples, out.reshape(-1)
        )
    if rc != 0:
        raise ValueError(
            "document list exhausted while packing samples — sizes/doc_idx "
            "inconsistent with num_samples"
        )
    return out


def build_bert_mapping(
    docs: np.ndarray,
    sizes: np.ndarray,
    *,
    num_epochs: int,
    max_num_samples: int,
    max_seq_length: int,
    short_seq_prob: float,
    seed: int,
) -> Optional[np.ndarray]:
    """BERT-style span mapping (parity: helpers.cpp build_mapping :261-511).
    Rows are (first_sentence, end_sentence, target_len), shuffled
    deterministically by seed."""
    lib = load()
    if lib is None:
        return None
    docs = np.ascontiguousarray(docs, dtype=np.int64)
    sizes = np.ascontiguousarray(sizes, dtype=np.int32)
    n_docs = len(docs) - 1
    args = (docs, n_docs, sizes, num_epochs, max_num_samples, max_seq_length, short_seq_prob, seed)
    n = lib.relora_count_bert_mapping(*args)
    maps = np.zeros((n, 3), dtype=np.int64)
    if n:
        lib.relora_fill_bert_mapping(*args, maps.reshape(-1))
    return maps


def build_blocks_mapping(
    docs: np.ndarray,
    sizes: np.ndarray,
    titles_sizes: np.ndarray,
    *,
    num_epochs: int,
    max_num_samples: int,
    max_seq_length: int,
    seed: int,
    use_one_sent_blocks: bool = False,
) -> Optional[np.ndarray]:
    """Block-span mapping, bit-identical to the reference's
    build_blocks_mapping (helpers.cpp:513-747) — golden-tested against its
    compiled module (tests/test_data_megatron.py).

    Rows are (span_start_sentence, span_end_sentence, doc, block_id), where
    the per-document target length is ``max_seq_length - titles_sizes[doc]``
    and block_id is a per-epoch running id; rows come Fisher-Yates shuffled
    with mt19937_64(seed + 1), exactly like the reference.  The output dtype
    follows the reference's rule: uint32 when the sentence count fits, else
    uint64."""
    lib = load()
    if lib is None:
        return None
    docs = np.ascontiguousarray(docs, dtype=np.int64)
    sizes = np.ascontiguousarray(sizes, dtype=np.int32)
    titles_sizes = np.ascontiguousarray(titles_sizes, dtype=np.int32)
    n_docs = len(docs) - 1
    args = (
        docs, n_docs, sizes, titles_sizes, num_epochs, max_num_samples,
        max_seq_length, int(use_one_sent_blocks),
    )
    n = lib.relora_count_blocks_mapping(*args)
    maps = np.zeros((n, 4), dtype=np.int64)
    if n:
        lib.relora_fill_blocks_mapping(*args, seed, maps.reshape(-1))
    out_dtype = np.uint32 if len(sizes) <= np.iinfo(np.uint32).max else np.uint64
    return maps.astype(out_dtype)


def build_blending_indices_native(
    weights: np.ndarray, size: int
) -> Optional[tuple[np.ndarray, np.ndarray]]:
    lib = load()
    if lib is None:
        return None
    weights = np.ascontiguousarray(weights, dtype=np.float64)
    dataset_index = np.zeros(size, dtype=np.uint8)
    dataset_sample_index = np.zeros(size, dtype=np.int64)
    lib.relora_build_blending_indices(
        dataset_index, dataset_sample_index, weights, len(weights), size
    )
    return dataset_index, dataset_sample_index
