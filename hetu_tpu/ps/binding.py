"""ctypes binding + on-demand build of the C++ PS core.

Reference analog: python/hetu/_base.py loading _LIB/libps.so via ctypes and
ps-lite/src/python_binding.cc (151 LoC C API).  We compile csrc/*.cpp with
g++ on first use (no cmake needed for four TUs) into
hetu_tpu/ps/_build/libhetu_ps.<hash of the sources>.so.

The library's name carries a hash of the source files' CONTENT, so a
library is only ever loaded if it was built from exactly the sources on
disk (a copied checkout may carry a ``_build/`` from other sources and any
mtimes), and it is written under a temporary name and renamed into place,
so several processes starting on a fresh copy cannot tear each other's
output.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from pathlib import Path

_HERE = Path(__file__).resolve().parent
_CSRC = _HERE.parent.parent / "csrc"
_SRCS = [_CSRC / "hetu_ps.cpp", _CSRC / "hetu_ps_van.cpp",
         _CSRC / "hetu_ps_group.cpp", _CSRC / "hetu_ps_rcache.cpp"]
_HDRS = [_CSRC / "hetu_ps_dtype.h"]  # hashed, not passed to g++
_BUILD = _HERE / "_build"
_CXX = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]

_lock = threading.Lock()
_lib = None
_err = None


def _so_path() -> Path:
    h = hashlib.sha256(" ".join(_CXX).encode())
    for src in _SRCS + _HDRS:
        h.update(src.name.encode())
        h.update(src.read_bytes())
    return _BUILD / f"libhetu_ps.{h.hexdigest()[:16]}.so"


def _build() -> Path:
    so = _so_path()
    if so.exists():
        return so
    _BUILD.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=_BUILD, prefix=so.name + ".", suffix=".tmp")
    os.close(fd)
    try:
        r = subprocess.run([*_CXX, *[str(s) for s in _SRCS], "-o", tmp],
                           capture_output=True, text=True)
        if r.returncode:
            raise RuntimeError(f"g++ failed building {so.name}:\n{r.stderr}")
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def _load():
    global _lib, _err
    with _lock:
        if _lib is not None or _err is not None:
            return _lib
        try:
            lib = ctypes.CDLL(str(_build()))
        except (OSError, RuntimeError) as e:  # no g++, failed build, bad .so
            _err = e
            return None
        c = ctypes
        i64p = c.POINTER(c.c_int64)
        i8p = c.POINTER(c.c_int8)
        f32p = c.POINTER(c.c_float)
        u64p = c.POINTER(c.c_uint64)
        u32p = c.POINTER(c.c_uint32)
        i32p = c.POINTER(c.c_int32)
        u8p = c.POINTER(c.c_uint8)
        sigs = {
            "ps_table_create": ([c.c_int, c.c_int64, c.c_int64, c.c_int,
                                 c.c_double, c.c_double, c.c_uint64], c.c_int),
            "ps_table_set_optimizer": ([c.c_int, c.c_int, c.c_float, c.c_float,
                                        c.c_float, c.c_float, c.c_float],
                                       c.c_int),
            "ps_table_clear": ([c.c_int], c.c_int),
            "ps_table_rows": ([c.c_int], c.c_int64),
            "ps_table_dim": ([c.c_int], c.c_int64),
            "ps_dense_pull": ([c.c_int, f32p], c.c_int),
            "ps_dense_push": ([c.c_int, f32p], c.c_int),
            "ps_dense_push_pull": ([c.c_int, f32p, f32p], c.c_int),
            "ps_sparse_pull": ([c.c_int, i64p, c.c_int64, f32p, u64p],
                               c.c_int),
            "ps_sparse_push": ([c.c_int, i64p, f32p, c.c_int64], c.c_int),
            "ps_sparse_push_pull": ([c.c_int, i64p, f32p, c.c_int64, f32p],
                                    c.c_int),
            "ps_sparse_set": ([c.c_int, i64p, f32p, c.c_int64], c.c_int),
            "ps_table_save": ([c.c_int, c.c_char_p], c.c_int),
            "ps_table_load": ([c.c_int, c.c_char_p], c.c_int),
            # server-side optimizer slot export/import (durable slots)
            "ps_table_slots_get": ([c.c_int, i64p, c.c_int64, f32p, f32p,
                                    u64p], c.c_int),
            "ps_table_slots_set": ([c.c_int, i64p, c.c_int64, f32p, f32p,
                                    u64p], c.c_int),
            "ps_van_table_slots_get": ([c.c_int, c.c_int, i64p, c.c_int64,
                                        c.c_int64, f32p, f32p, u64p],
                                       c.c_int),
            "ps_van_table_slots_set": ([c.c_int, c.c_int, i64p, c.c_int64,
                                        c.c_int64, f32p, f32p, u64p],
                                       c.c_int),
            "ps_group_slots_get": ([c.c_int, i64p, c.c_int64, f32p, f32p,
                                    u64p], c.c_int),
            "ps_group_slots_set": ([c.c_int, i64p, f32p, f32p, u64p,
                                    c.c_int64], c.c_int),
            "ps_ssp_init": ([c.c_int, c.c_int, c.c_int], c.c_int),
            "ps_ssp_clock_and_wait": ([c.c_int, c.c_int, c.c_int], c.c_int),
            "ps_ssp_get_clock": ([c.c_int, c.c_int], c.c_int64),
            "ps_preduce_get_partner": ([c.c_int, c.c_int, c.c_int,
                                        c.c_int], c.c_uint64),
            "ps_cache_create": ([c.c_int, c.c_int, c.c_int64, c.c_int],
                                c.c_int),
            "ps_cache_lookup": ([c.c_int, i64p, c.c_int64, c.c_uint64, f32p],
                                c.c_int64),
            "ps_cache_update": ([c.c_int, i64p, f32p, c.c_int64], c.c_int),
            "ps_cache_flush": ([c.c_int], c.c_int),
            "ps_cache_size": ([c.c_int], c.c_int64),
            # TCP van (multi-host transport, csrc/hetu_ps_van.cpp)
            "ps_van_start": ([c.c_int], c.c_int),
            "ps_van_stop": ([], None),
            "ps_van_connect": ([c.c_char_p, c.c_int], c.c_int),
            "ps_van_close": ([c.c_int], None),
            "ps_van_ping": ([c.c_int], c.c_int),
            "ps_van_table_create": ([c.c_int, c.c_int, c.c_int64, c.c_int64,
                                     c.c_int, c.c_double, c.c_double,
                                     c.c_uint64], c.c_int),
            "ps_van_set_optimizer": ([c.c_int, c.c_int, c.c_int, c.c_float,
                                      c.c_float, c.c_float, c.c_float,
                                      c.c_float], c.c_int),
            "ps_van_sparse_pull": ([c.c_int, c.c_int, i64p, c.c_int64, f32p,
                                    c.c_int64], c.c_int),
            "ps_van_sparse_push": ([c.c_int, c.c_int, i64p, f32p, c.c_int64,
                                    c.c_int64], c.c_int),
            "ps_van_dense_pull": ([c.c_int, c.c_int, f32p, c.c_int64],
                                  c.c_int),
            "ps_van_dense_push": ([c.c_int, c.c_int, f32p, c.c_int64],
                                  c.c_int),
            "ps_van_sparse_set": ([c.c_int, c.c_int, i64p, f32p, c.c_int64,
                                   c.c_int64], c.c_int),
            "ps_van_dense_push_id": ([c.c_int, c.c_int, f32p, c.c_int64,
                                      c.c_uint64], c.c_int),
            "ps_van_sparse_push_id": ([c.c_int, c.c_int, i64p, f32p,
                                       c.c_int64, c.c_int64, c.c_uint64],
                                      c.c_int),
            # single-row compare-and-set (controller-claim primitive)
            "ps_van_row_cas": ([c.c_int, c.c_int, c.c_int64, c.c_int,
                                c.c_float, f32p, c.c_int64, f32p], c.c_int),
            "ps_van_table_clear": ([c.c_int, c.c_int], c.c_int),
            "ps_van_table_save": ([c.c_int, c.c_int, c.c_char_p], c.c_int),
            "ps_van_table_load": ([c.c_int, c.c_int, c.c_char_p], c.c_int),
            # partitioned multi-server group (csrc/hetu_ps_group.cpp)
            "ps_group_create": ([c.c_char_p, c.c_int, c.c_int64, c.c_int64,
                                 c.c_int, c.c_double, c.c_double, c.c_uint64,
                                 c.c_double, c.c_int], c.c_int),
            "ps_group_create_dt": ([c.c_char_p, c.c_int, c.c_int64,
                                    c.c_int64, c.c_int, c.c_double,
                                    c.c_double, c.c_uint64, c.c_double,
                                    c.c_int, c.c_int], c.c_int),
            "ps_group_set_optimizer": ([c.c_int, c.c_int, c.c_float,
                                        c.c_float, c.c_float, c.c_float,
                                        c.c_float], c.c_int),
            "ps_group_n": ([c.c_int], c.c_int),
            "ps_group_start": ([c.c_int, c.c_int], c.c_int64),
            "ps_group_sparse_pull": ([c.c_int, i64p, c.c_int64, f32p],
                                     c.c_int),
            "ps_group_sparse_push": ([c.c_int, i64p, f32p, c.c_int64],
                                     c.c_int),
            "ps_group_sparse_set": ([c.c_int, i64p, f32p, c.c_int64],
                                    c.c_int),
            "ps_group_dense_pull": ([c.c_int, f32p], c.c_int),
            "ps_group_dense_push": ([c.c_int, f32p], c.c_int),
            "ps_group_save": ([c.c_int, c.c_char_p], c.c_int),
            "ps_group_load": ([c.c_int, c.c_char_p], c.c_int),
            "ps_group_alive_mask": ([c.c_int], c.c_uint64),
            "ps_group_recovered": ([c.c_int], c.c_uint64),
            "ps_group_close": ([c.c_int], None),
            # HET cache tier on the wire + scheduler role (round 4)
            "ps_sync_pull": ([c.c_int, i64p, u64p, c.c_int64, c.c_uint64,
                              u32p, u64p, f32p], c.c_int64),
            "ps_van_sync_pull": ([c.c_int, c.c_int, i64p, u64p, c.c_int64,
                                  c.c_uint64, c.c_int64, u32p, u64p, f32p],
                                 c.c_int64),
            "ps_van_push_sync": ([c.c_int, c.c_int, i64p, f32p, c.c_int64,
                                  i64p, u64p, c.c_int64, c.c_uint64,
                                  c.c_int64, c.c_uint64, u32p, u64p, f32p],
                                 c.c_int64),
            "ps_van_ssp_init": ([c.c_int, c.c_int, c.c_int, c.c_int],
                                c.c_int),
            "ps_van_ssp_clock": ([c.c_int, c.c_int, c.c_int, c.c_int],
                                 c.c_int),
            "ps_van_ssp_get": ([c.c_int, c.c_int, c.c_int], c.c_int64),
            "ps_van_preduce": ([c.c_int, c.c_int, c.c_int, c.c_int,
                                c.c_int], c.c_uint64),
            "ps_van_sched_register": ([c.c_int, c.c_int, c.c_int, c.c_int],
                                      c.c_int),
            "ps_van_sched_map": ([c.c_int, c.c_int, i32p, u8p, i32p,
                                  c.c_char_p], c.c_int),
            "ps_sched_beat_start": ([c.c_char_p, c.c_int, c.c_int, c.c_int,
                                     c.c_int, c.c_double], c.c_int),
            "ps_sched_beat_rank": ([c.c_int], c.c_int),
            "ps_sched_beat_stop": ([c.c_int], None),
            "ps_group_create_sched": ([c.c_char_p, c.c_int, c.c_int, c.c_int,
                                       c.c_int64, c.c_int64, c.c_int,
                                       c.c_double, c.c_double, c.c_uint64,
                                       c.c_double, c.c_int], c.c_int),
            "ps_group_create_sched_dt": ([c.c_char_p, c.c_int, c.c_int,
                                          c.c_int, c.c_int64, c.c_int64,
                                          c.c_int, c.c_double, c.c_double,
                                          c.c_uint64, c.c_double, c.c_int,
                                          c.c_int], c.c_int),
            "ps_group_rows": ([c.c_int], c.c_int64),
            "ps_group_dim": ([c.c_int], c.c_int64),
            "ps_group_sync_pull": ([c.c_int, i64p, u64p, c.c_int64,
                                    c.c_uint64, u32p, u64p, f32p], c.c_int64),
            "ps_group_push_sync": ([c.c_int, i64p, f32p, c.c_int64, i64p,
                                    u64p, c.c_int64, c.c_uint64, u32p, u64p,
                                    f32p], c.c_int64),
            # dtype'd rows: bf16/int8 storage + wire encoding (round 5)
            "ps_table_create_ex": ([c.c_int, c.c_int64, c.c_int64, c.c_int,
                                    c.c_double, c.c_double, c.c_uint64,
                                    c.c_int], c.c_int),
            "ps_table_dtype": ([c.c_int], c.c_int),
            "ps_van_table_create_dt": ([c.c_int, c.c_int, c.c_int64,
                                        c.c_int64, c.c_int, c.c_double,
                                        c.c_double, c.c_uint64, c.c_int],
                                       c.c_int),
            "ps_van_sparse_pull_dt": ([c.c_int, c.c_int, i64p, c.c_int64,
                                       f32p, c.c_int64, c.c_int], c.c_int),
            "ps_van_sparse_set_dt": ([c.c_int, c.c_int, i64p, f32p,
                                      c.c_int64, c.c_int64, c.c_int],
                                     c.c_int),
            "ps_van_sparse_push_dt": ([c.c_int, c.c_int, i64p, f32p,
                                       c.c_int64, c.c_int64, c.c_int],
                                      c.c_int),
            "ps_van_sparse_push_id_dt": ([c.c_int, c.c_int, i64p, f32p,
                                          c.c_int64, c.c_int64, c.c_int,
                                          c.c_uint64], c.c_int),
            "ps_van_stats": ([c.c_int, u64p, u64p, u64p], c.c_int),
            # direct q8 codec + negotiated quantized wire (round 8)
            "ps_q8_encode": ([f32p, c.c_int64, c.c_int64, i8p, f32p],
                             c.c_int),
            "ps_q8_decode": ([i8p, f32p, c.c_int64, c.c_int64, f32p],
                             c.c_int),
            "ps_van_dense_push_w": ([c.c_int, c.c_int, f32p, c.c_int64,
                                     c.c_int64, c.c_int, c.c_uint64, f32p],
                                    c.c_int),
            "ps_van_dense_pull_w": ([c.c_int, c.c_int, f32p, c.c_int64,
                                     c.c_int64, c.c_int], c.c_int),
            "ps_van_sparse_push_w": ([c.c_int, c.c_int, i64p, f32p,
                                      c.c_int64, c.c_int64, c.c_int,
                                      c.c_uint64, f32p], c.c_int),
            # bulk-blob channel + barrier + frame stats (round 5)
            "ps_van_blob_put": ([c.c_int, c.c_int64, c.c_uint64, c.c_void_p,
                                 c.c_int64, c.c_int], c.c_int),
            "ps_van_blob_get": ([c.c_int, c.c_int64, c.c_uint64, c.c_void_p,
                                 c.c_int64, c.c_int, i64p], c.c_int64),
            "ps_van_blob_ack": ([c.c_int, c.c_int64, c.c_uint64], c.c_int),
            "ps_van_barrier": ([c.c_int, c.c_int64, c.c_int, c.c_int],
                               c.c_int),
            "ps_van_stats_frames": ([c.c_int], c.c_int64),
            "ps_rcache_create": ([c.c_int, c.c_int64, c.c_int, c.c_float],
                                 c.c_int),
            "ps_rcache_lookup": ([c.c_int, i64p, c.c_int64, c.c_uint64,
                                  f32p], c.c_int64),
            "ps_rcache_update": ([c.c_int, i64p, f32p, c.c_int64], c.c_int),
            "ps_rcache_flush": ([c.c_int], c.c_int),
            "ps_rcache_size": ([c.c_int], c.c_int64),
            "ps_rcache_close": ([c.c_int], None),
        }
        for name, (argtypes, restype) in sigs.items():
            fn = getattr(lib, name)
            fn.argtypes = argtypes
            fn.restype = restype
        _lib = lib
        return _lib


class _Lazy:
    def __getattr__(self, name):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"hetu_ps native lib unavailable: {_err}")
        return getattr(lib, name)


lib = _Lazy()


def available() -> bool:
    """Whether the native library built and loaded.  For tests that skip
    without a toolchain; code that NEEDS the library just uses ``lib`` and
    gets the build error raised."""
    return _load() is not None
