"""C++ native runtime tests (csrc/): data feed, TCP store, sparse table,
profiler. Reference parity: C++ gtest tier (framework/data_feed_test,
gen_comm_id, table tests) driven through the ctypes surface."""
import os
import tempfile
import threading

import numpy as np
import pytest

from paddle_tpu.core.native import (load_native, NativeDataFeed, TCPStore,
                                    NativeSparseTable)

pytestmark = pytest.mark.skipif(load_native() is None,
                                reason="native lib unavailable")


class TestDataFeed:
    def _write_files(self, tmp, n_files=3, rows=50):
        files = []
        rng = np.random.RandomState(0)
        expect = []
        for fi in range(n_files):
            path = os.path.join(tmp, f"part-{fi}")
            with open(path, 'w') as f:
                for r in range(rows):
                    feats = rng.rand(4)
                    label = rng.randint(0, 2)
                    f.write(' '.join(f"{v:.6f}" for v in feats) +
                            f" | {label}\n")
                    expect.append((feats, label))
            files.append(path)
        return files, expect

    def test_streaming_batches(self):
        with tempfile.TemporaryDirectory() as tmp:
            files, expect = self._write_files(tmp)
            feed = NativeDataFeed([(4, 'float'), (1, 'int64')],
                                  batch_size=32, num_threads=2)
            feed.set_filelist(files)
            feed.start()
            total = 0
            for f, i in feed:
                assert f.shape[1] == 4 and i.shape[1] == 1
                assert np.all((i >= 0) & (i <= 1))
                total += len(f)
            assert total == 150

    def test_in_memory_shuffle_epochs(self):
        with tempfile.TemporaryDirectory() as tmp:
            files, _ = self._write_files(tmp, n_files=2, rows=40)
            feed = NativeDataFeed([(4, 'float'), (1, 'int64')],
                                  batch_size=16)
            feed.set_filelist(files)
            feed.load_into_memory(seed=7)
            assert feed.memory_size() == 80
            e1 = np.concatenate([f for f, _ in feed.iter_memory()])
            feed.rewind(reshuffle=False)
            e2 = np.concatenate([f for f, _ in feed.iter_memory()])
            np.testing.assert_allclose(e1, e2)
            feed.rewind(reshuffle=True, seed=99)
            e3 = np.concatenate([f for f, _ in feed.iter_memory()])
            assert not np.allclose(e1, e3)
            assert np.allclose(np.sort(e1.ravel()), np.sort(e3.ravel()))


class TestTCPStore:
    def test_set_get_add(self):
        master = TCPStore(is_master=True)
        client = TCPStore(port=master.port)
        client.set('nccl_id_equiv', b'\x01\x02\x03coordinator:1234')
        assert master.get('nccl_id_equiv') == b'\x01\x02\x03coordinator:1234'
        assert client.get('missing', wait=False) is None
        assert client.add('counter', 5) == 5
        assert master.add('counter', 2) == 7
        client.close()
        master.close()

    def test_wait_blocks_until_set(self):
        master = TCPStore(is_master=True)
        client = TCPStore(port=master.port)
        result = {}

        def waiter():
            result['v'] = client.get('late_key', wait=True)

        t = threading.Thread(target=waiter)
        t.start()
        import time
        time.sleep(0.2)
        assert 'v' not in result
        master.set('late_key', b'hello')
        t.join(timeout=5)
        assert result.get('v') == b'hello'
        client.close()
        master.close()

    def test_barrier_releases_together(self):
        """2-party barrier (parity: gloo barrier / role_maker rendezvous)."""
        master = TCPStore(is_master=True)
        c2 = TCPStore(port=master.port)
        order = []

        def party(store, name):
            store.barrier('b1', 2)
            order.append(name)

        t1 = threading.Thread(target=party, args=(master, 'a'))
        t2 = threading.Thread(target=party, args=(c2, 'b'))
        t1.start()
        import time
        time.sleep(0.2)
        assert not order  # first party still blocked
        t2.start()
        t1.join(5)
        t2.join(5)
        assert sorted(order) == ['a', 'b']
        c2.close()
        master.close()


class TestSparseTable:
    def test_pull_push_adagrad(self):
        table = NativeSparseTable(dim=8, optimizer='adagrad', seed=42)
        ids = np.array([1, 5, 9, 5])
        rows = table.pull(ids)
        assert rows.shape == (4, 8)
        np.testing.assert_allclose(rows[1], rows[3])  # same id, same row
        assert len(table) == 3
        # deterministic on-miss init by (seed, id)
        table2 = NativeSparseTable(dim=8, optimizer='adagrad', seed=42)
        np.testing.assert_allclose(table2.pull(np.array([1]))[0], rows[0])

        grads = np.ones((4, 8), np.float32)
        table.push(ids, grads, lr=0.1)
        after = table.pull(ids)
        assert np.all(after < rows)  # positive grads decrease weights

    def test_save_load_shrink(self):
        with tempfile.TemporaryDirectory() as tmp:
            t = NativeSparseTable(dim=4, optimizer='sgd')
            ids = np.arange(100)
            rows = t.pull(ids)
            path = os.path.join(tmp, 'table.bin')
            t.save(path)
            t2 = NativeSparseTable(dim=4, optimizer='sgd')
            t2.load(path)
            assert len(t2) == 100
            np.testing.assert_allclose(t2.pull(ids), rows)
            dropped = t2.shrink(threshold=1e9)
            assert dropped == 100 and len(t2) == 0

    def test_scale_1m_ids(self):
        """Throughput sanity on 1M-row pulls (trillion-scale is sharded
        across hosts; per-host throughput is what matters here)."""
        import time
        t = NativeSparseTable(dim=16, optimizer='adagrad')
        ids = np.random.RandomState(0).randint(0, 10_000_000, 100_000)
        t0 = time.time()
        out = t.pull(ids)
        dt = time.time() - t0
        assert out.shape == (100_000, 16)
        assert dt < 5.0, f"pull too slow: {dt}s"


class TestProfiler:
    def test_record_summary_export(self):
        import paddle_tpu.profiler as prof
        prof.reset_profiler()
        prof.start_profiler()
        with prof.RecordEvent("matmul_dispatch"):
            sum(range(1000))
        with prof.RecordEvent("matmul_dispatch"):
            sum(range(1000))
        with prof.RecordEvent("data_feed"):
            pass
        s = prof.summary()
        assert "matmul_dispatch" in s and "data_feed" in s
        with tempfile.TemporaryDirectory() as tmp:
            p = os.path.join(tmp, 'trace.json')
            prof.export_chrome_tracing(p)
            import json
            with open(p) as f:
                trace = json.load(f)
            # the view since start_profiler(): three spans (plus the
            # process/thread name records of the chrome format)
            assert len([e for e in trace['traceEvents']
                        if e['ph'] == 'X']) == 3
        prof.stop_profiler(profile_path=None)


def test_cpp_extension_custom_op():
    """Parity: utils.cpp_extension.load — user C++ op JIT-built + called."""
    import tempfile
    from paddle_tpu.utils import cpp_extension
    from paddle_tpu.core.tensor import Tensor
    src = os.path.join(tempfile.mkdtemp(), 'my_ops.cc')
    with open(src, 'w') as f:
        f.write('''
#include <cstdint>
extern "C" void my_relu6(const float* in, float* out, int64_t n) {
  for (int64_t i = 0; i < n; ++i) {
    float v = in[i] < 0 ? 0 : in[i];
    out[i] = v > 6 ? 6 : v;
  }
}
''')
    mod = cpp_extension.load('my_ext', [src])
    x = Tensor(np.array([-1.0, 3.0, 9.0], np.float32))
    out = mod.my_relu6(x)
    np.testing.assert_allclose(out.numpy(), [0.0, 3.0, 6.0])
