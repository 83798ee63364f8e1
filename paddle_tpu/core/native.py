"""ctypes bindings to the C++ native runtime (csrc/).

Reference parity: the pybind layer (paddle/fluid/pybind — N33) for the
runtime-services subset that stays native in the TPU rebuild: data feed
(N19), TCP store rendezvous (N8/N9), sparse PS table (N30). (The host
profiler, N4, is the Python span ring of `paddle_tpu/profiler.py`; its
native mirror went in PRs 25 and 36.) Builds csrc/ with make at first load (g++ only — no pybind11 dependency;
plain C ABI + ctypes).
"""
import ctypes
import os
import subprocess

import numpy as np

_LIB = None
_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), 'csrc')
_SO = os.path.join(_CSRC, 'libpaddle_tpu_native.so')


def load_native(required=False):
    """Load the native library, letting `make` decide whether csrc/
    needs (re)building — the .so is untracked, so a binary left on disk
    by an older csrc/*.cc must not be what loads. Returns None when
    unavailable and not required."""
    global _LIB
    if _LIB is not None:
        return _LIB
    try:
        subprocess.run(['make', '-C', _CSRC], check=True,
                       capture_output=True)
    except (OSError, subprocess.CalledProcessError) as e:
        if required:
            detail = getattr(e, 'stderr', b'') or b''
            raise RuntimeError(
                f"native build failed: {e}\n"
                f"{detail.decode(errors='replace')}")
        return None
    try:
        lib = ctypes.CDLL(_SO)
    except OSError as e:
        if required:
            raise
        return None

    # datafeed
    lib.ptpu_datafeed_create.restype = ctypes.c_void_p
    lib.ptpu_datafeed_create.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.ptpu_datafeed_set_files.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p), ctypes.c_int]
    lib.ptpu_datafeed_start.argtypes = [ctypes.c_void_p]
    lib.ptpu_datafeed_next.restype = ctypes.c_int
    lib.ptpu_datafeed_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                       ctypes.c_void_p]
    lib.ptpu_datafeed_load_shuffle.argtypes = [ctypes.c_void_p,
                                               ctypes.c_uint64]
    lib.ptpu_datafeed_next_mem.restype = ctypes.c_int
    lib.ptpu_datafeed_next_mem.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                           ctypes.c_void_p]
    lib.ptpu_datafeed_rewind.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                         ctypes.c_uint64]
    lib.ptpu_datafeed_memory_size.restype = ctypes.c_int64
    lib.ptpu_datafeed_memory_size.argtypes = [ctypes.c_void_p]
    lib.ptpu_datafeed_destroy.argtypes = [ctypes.c_void_p]

    # tcp store
    lib.ptpu_store_server_start.restype = ctypes.c_void_p
    lib.ptpu_store_server_start.argtypes = [ctypes.c_int]
    lib.ptpu_store_server_port.restype = ctypes.c_int
    lib.ptpu_store_server_port.argtypes = [ctypes.c_void_p]
    lib.ptpu_store_server_stop.argtypes = [ctypes.c_void_p]
    lib.ptpu_store_client_connect.restype = ctypes.c_void_p
    lib.ptpu_store_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                              ctypes.c_int]
    lib.ptpu_store_set.restype = ctypes.c_int
    lib.ptpu_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_int]
    lib.ptpu_store_get.restype = ctypes.c_int
    lib.ptpu_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_int,
                                   ctypes.c_int]
    lib.ptpu_store_add.restype = ctypes.c_int64
    lib.ptpu_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int64]
    lib.ptpu_store_barrier.restype = ctypes.c_int
    lib.ptpu_store_barrier.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_uint32]
    lib.ptpu_store_client_close.argtypes = [ctypes.c_void_p]

    # sparse table
    lib.ptpu_table_create.restype = ctypes.c_void_p
    lib.ptpu_table_create.argtypes = [ctypes.c_int, ctypes.c_int,
                                      ctypes.c_int, ctypes.c_float,
                                      ctypes.c_uint64]
    lib.ptpu_table_create2.restype = ctypes.c_void_p
    lib.ptpu_table_create2.argtypes = [ctypes.c_int, ctypes.c_int,
                                       ctypes.c_int, ctypes.c_float,
                                       ctypes.c_uint64, ctypes.c_float,
                                       ctypes.c_float, ctypes.c_float]
    lib.ptpu_ssd_table_create.restype = ctypes.c_void_p
    lib.ptpu_ssd_table_create.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_float,
        ctypes.c_uint64, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ctypes.c_int64, ctypes.c_char_p]
    lib.ptpu_ssd_mem_rows.restype = ctypes.c_int64
    lib.ptpu_ssd_mem_rows.argtypes = [ctypes.c_void_p]
    lib.ptpu_ssd_total_rows.restype = ctypes.c_int64
    lib.ptpu_ssd_total_rows.argtypes = [ctypes.c_void_p]
    lib.ptpu_ssd_flush.argtypes = [ctypes.c_void_p]
    lib.ptpu_ssd_recover.restype = ctypes.c_int
    lib.ptpu_ssd_recover.argtypes = [ctypes.c_void_p]
    lib.ptpu_ssd_save.restype = ctypes.c_int
    lib.ptpu_ssd_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptpu_ssd_load.restype = ctypes.c_int
    lib.ptpu_ssd_load.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptpu_table_pull.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_int, ctypes.c_void_p]
    lib.ptpu_table_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_int, ctypes.c_void_p,
                                    ctypes.c_float]
    lib.ptpu_table_set.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   ctypes.c_int, ctypes.c_void_p]
    lib.ptpu_table_size.restype = ctypes.c_int64
    lib.ptpu_table_size.argtypes = [ctypes.c_void_p]
    lib.ptpu_table_shrink.restype = ctypes.c_int64
    lib.ptpu_table_shrink.argtypes = [ctypes.c_void_p, ctypes.c_float]
    lib.ptpu_table_save.restype = ctypes.c_int
    lib.ptpu_table_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptpu_table_load.restype = ctypes.c_int
    lib.ptpu_table_load.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptpu_table_destroy.argtypes = [ctypes.c_void_p]

    # dense table
    lib.ptpu_dense_create.restype = ctypes.c_void_p
    lib.ptpu_dense_create.argtypes = [ctypes.c_int64, ctypes.c_int]
    lib.ptpu_dense_set.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.ptpu_dense_pull.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.ptpu_dense_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_float]
    lib.ptpu_dense_size.restype = ctypes.c_int64
    lib.ptpu_dense_size.argtypes = [ctypes.c_void_p]
    lib.ptpu_dense_save.restype = ctypes.c_int
    lib.ptpu_dense_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptpu_dense_load.restype = ctypes.c_int
    lib.ptpu_dense_load.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptpu_dense_destroy.argtypes = [ctypes.c_void_p]

    _LIB = lib
    return lib


class NativeDataFeed:
    """Parity: framework/data_feed.cc MultiSlotDataFeed through C++."""

    def __init__(self, slots, batch_size, num_threads=2,
                 channel_capacity=4096):
        """slots: list of (width, kind) with kind in {'float','int64'}."""
        self.lib = load_native(required=True)
        widths = (ctypes.c_int * len(slots))(*[w for w, _ in slots])
        isf = (ctypes.c_int * len(slots))(
            *[1 if k == 'float' else 0 for _, k in slots])
        self.h = self.lib.ptpu_datafeed_create(
            widths, isf, len(slots), batch_size, num_threads,
            channel_capacity)
        self.batch_size = batch_size
        self.fwidth = sum(w for w, k in slots if k == 'float')
        self.iwidth = sum(w for w, k in slots if k == 'int64')

    def set_filelist(self, files):
        arr = (ctypes.c_char_p * len(files))(
            *[f.encode() for f in files])
        self.lib.ptpu_datafeed_set_files(self.h, arr, len(files))

    def start(self):
        self.lib.ptpu_datafeed_start(self.h)

    def _buffers(self):
        f = np.empty((self.batch_size, self.fwidth), np.float32) \
            if self.fwidth else None
        i = np.empty((self.batch_size, self.iwidth), np.int64) \
            if self.iwidth else None
        return f, i

    def __iter__(self):
        while True:
            f, i = self._buffers()
            n = self.lib.ptpu_datafeed_next(
                self.h,
                f.ctypes.data_as(ctypes.c_void_p) if f is not None else None,
                i.ctypes.data_as(ctypes.c_void_p) if i is not None else None)
            if n == 0:
                return
            yield (f[:n] if f is not None else None,
                   i[:n] if i is not None else None)

    def load_into_memory(self, seed=0):
        self.lib.ptpu_datafeed_load_shuffle(self.h, seed)

    def memory_size(self):
        return self.lib.ptpu_datafeed_memory_size(self.h)

    def iter_memory(self):
        while True:
            f, i = self._buffers()
            n = self.lib.ptpu_datafeed_next_mem(
                self.h,
                f.ctypes.data_as(ctypes.c_void_p) if f is not None else None,
                i.ctypes.data_as(ctypes.c_void_p) if i is not None else None)
            if n == 0:
                return
            yield (f[:n] if f is not None else None,
                   i[:n] if i is not None else None)

    def rewind(self, reshuffle=False, seed=0):
        self.lib.ptpu_datafeed_rewind(self.h, 1 if reshuffle else 0, seed)

    def __del__(self):
        if getattr(self, 'h', None) and self.lib:
            self.lib.ptpu_datafeed_destroy(self.h)
            self.h = None


class TCPStore:
    """Parity: gen_comm_id_helper SocketServer + Gloo KV (N8/N9)."""

    def __init__(self, host='127.0.0.1', port=0, is_master=False,
                 timeout=60):
        self.lib = load_native(required=True)
        self.server = None
        if is_master:
            self.server = self.lib.ptpu_store_server_start(port)
            if not self.server:
                raise RuntimeError(f"TCPStore: bind failed on port {port}")
            port = self.lib.ptpu_store_server_port(self.server)
        self.port = port
        self.host = host
        self.client = self.lib.ptpu_store_client_connect(
            host.encode(), port, timeout)
        if not self.client:
            raise RuntimeError(f"TCPStore: connect to {host}:{port} failed")

    def set(self, key, value):
        if isinstance(value, str):
            value = value.encode()
        ok = self.lib.ptpu_store_set(self.client, key.encode(), value,
                                     len(value))
        if not ok:
            raise RuntimeError("TCPStore.set failed")

    def get(self, key, wait=True):
        cap = 1 << 20
        buf = ctypes.create_string_buffer(cap)
        n = self.lib.ptpu_store_get(self.client, key.encode(), buf, cap,
                                    1 if wait else 0)
        if n < 0:
            return None
        return buf.raw[:n]

    def add(self, key, delta=1):
        return self.lib.ptpu_store_add(self.client, key.encode(), delta)

    def barrier(self, key, world_size):
        ok = self.lib.ptpu_store_barrier(self.client, key.encode(),
                                         world_size)
        if not ok:
            raise RuntimeError("TCPStore.barrier failed")

    def close(self):
        if getattr(self, 'client', None):
            self.lib.ptpu_store_client_close(self.client)
            self.client = None
        if getattr(self, 'server', None):
            self.lib.ptpu_store_server_stop(self.server)
            self.server = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeSparseTable:
    """Parity: distributed/table CommonSparseTable + heterPS hashtable."""

    SGD = 0
    ADAGRAD = 1
    ADAM = 2
    _OPTS = {'sgd': SGD, 'adagrad': ADAGRAD, 'adam': ADAM}

    def __init__(self, dim, num_shards=16, optimizer='adagrad',
                 init_range=0.05, seed=0, beta1=0.9, beta2=0.999,
                 eps=1e-8):
        self.lib = load_native(required=True)
        self.dim = dim
        opt = self._OPTS.get(optimizer, self.SGD)
        self.h = self.lib.ptpu_table_create2(dim, num_shards, opt,
                                             init_range, seed, beta1,
                                             beta2, eps)

    def pull(self, ids):
        ids = np.ascontiguousarray(ids, np.int64).reshape(-1)
        out = np.empty((len(ids), self.dim), np.float32)
        self.lib.ptpu_table_pull(
            self.h, ids.ctypes.data_as(ctypes.c_void_p), len(ids),
            out.ctypes.data_as(ctypes.c_void_p))
        return out

    def push(self, ids, grads, lr=0.01):
        ids = np.ascontiguousarray(ids, np.int64).reshape(-1)
        grads = np.ascontiguousarray(grads, np.float32).reshape(
            len(ids), self.dim)
        self.lib.ptpu_table_push(
            self.h, ids.ctypes.data_as(ctypes.c_void_p), len(ids),
            grads.ctypes.data_as(ctypes.c_void_p), lr)

    def set(self, ids, rows):
        """Assign embedding values (optimizer state untouched)."""
        ids = np.ascontiguousarray(ids, np.int64).reshape(-1)
        rows = np.ascontiguousarray(rows, np.float32).reshape(
            len(ids), self.dim)
        self.lib.ptpu_table_set(
            self.h, ids.ctypes.data_as(ctypes.c_void_p), len(ids),
            rows.ctypes.data_as(ctypes.c_void_p))

    def __len__(self):
        return self.lib.ptpu_table_size(self.h)

    def shrink(self, threshold):
        return self.lib.ptpu_table_shrink(self.h, threshold)

    def save(self, path):
        if not self.lib.ptpu_table_save(self.h, path.encode()):
            raise IOError(f"table save failed: {path}")

    def load(self, path):
        if not self.lib.ptpu_table_load(self.h, path.encode()):
            raise IOError(f"table load failed: {path}")

    def __del__(self):
        if getattr(self, 'h', None) and self.lib:
            self.lib.ptpu_table_destroy(self.h)
            self.h = None


class NativeSsdSparseTable(NativeSparseTable):
    """Parity: distributed/table/ssd_sparse_table.h — hot rows in memory
    under a row budget, cold rows spilled to per-shard append-only logs
    (the rocksdb analogue); Recover() rebuilds the index after a crash."""

    def __init__(self, dim, path, num_shards=16, optimizer='adagrad',
                 init_range=0.05, seed=0, beta1=0.9, beta2=0.999,
                 eps=1e-8, mem_budget_rows=1 << 20):
        import os as _os
        self.lib = load_native(required=True)
        self.dim = dim
        self.path = path
        _os.makedirs(path, exist_ok=True)
        opt = self._OPTS.get(optimizer, self.SGD)
        self.h = self.lib.ptpu_ssd_table_create(
            dim, num_shards, opt, init_range, seed, beta1, beta2, eps,
            mem_budget_rows, path.encode())

    def mem_rows(self):
        return self.lib.ptpu_ssd_mem_rows(self.h)

    def total_rows(self):
        return self.lib.ptpu_ssd_total_rows(self.h)

    def flush(self):
        """Spill all hot rows to the logs (checkpoint/shutdown)."""
        self.lib.ptpu_ssd_flush(self.h)

    def recover(self):
        """Rebuild the id→offset index from the logs after a restart."""
        if not self.lib.ptpu_ssd_recover(self.h):
            raise IOError(f"ssd table recover failed: {self.path}")

    def __len__(self):
        return self.total_rows()      # base Size() counts hot rows only

    def save(self, path):
        """Full snapshot incl. cold rows (streamed, never in RAM)."""
        if not self.lib.ptpu_ssd_save(self.h, path.encode()):
            raise IOError(f"ssd table save failed: {path}")

    def load(self, path):
        """Restore a snapshot straight into the spill logs."""
        if not self.lib.ptpu_ssd_load(self.h, path.encode()):
            raise IOError(f"ssd table load failed: {path}")


class NativeDenseTable:
    """Parity: distributed/table/common_dense_table.h — a fixed-size
    parameter block with the optimizer applied server-side."""

    def __init__(self, size, optimizer='sgd'):
        self.lib = load_native(required=True)
        self.size = int(size)
        opt = NativeSparseTable._OPTS.get(optimizer, 0)
        self.h = self.lib.ptpu_dense_create(self.size, opt)

    def set(self, values):
        v = np.ascontiguousarray(values, np.float32).reshape(-1)
        assert len(v) == self.size
        self.lib.ptpu_dense_set(self.h, v.ctypes.data_as(ctypes.c_void_p))

    def pull(self):
        out = np.empty(self.size, np.float32)
        self.lib.ptpu_dense_pull(self.h,
                                 out.ctypes.data_as(ctypes.c_void_p))
        return out

    def push(self, grad, lr=0.01):
        g = np.ascontiguousarray(grad, np.float32).reshape(-1)
        self.lib.ptpu_dense_push(self.h,
                                 g.ctypes.data_as(ctypes.c_void_p), lr)

    def save(self, path):
        if not self.lib.ptpu_dense_save(self.h, path.encode()):
            raise IOError(f"dense table save failed: {path}")

    def load(self, path):
        if not self.lib.ptpu_dense_load(self.h, path.encode()):
            raise IOError(f"dense table load failed: {path}")

    def __len__(self):
        return self.size

    def __del__(self):
        if getattr(self, 'h', None) and self.lib:
            self.lib.ptpu_dense_destroy(self.h)
            self.h = None
