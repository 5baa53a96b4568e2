"""ThreadGroup, memory pools, profiler, indexed recordio writer, stdin
split, cluster backend generators (reference: thread_group.h, memory.h,
timer.h, indexed_recordio, single_file_split, tracker backends)."""

import subprocess
import sys
import time

import numpy as np
import pytest

from dmlc_tpu.io.input_split import InputSplit
from dmlc_tpu.io.recordio import IndexedRecordIOWriter, RECORDIO_MAGIC
from dmlc_tpu.io.stream import create_stream
from dmlc_tpu.parallel.backends import (
    kubernetes_manifest, mpi_command, sge_script, slurm_script,
)
from dmlc_tpu.utils.logging import DMLCError
from dmlc_tpu.utils.memory import BufferPool, thread_local_pool
from dmlc_tpu.utils.thread_group import ManualEvent, ThreadGroup


class TestThreadGroup:
    def test_create_join(self):
        g = ThreadGroup()
        results = []
        g.create("worker-a", lambda: results.append("a"))
        g.create("worker-b", lambda x: results.append(x), "b")
        g.join_all()
        assert sorted(results) == ["a", "b"]
        assert g.size() == 0

    def test_cooperative_shutdown(self):
        g = ThreadGroup()
        stopped = []

        def worker():
            t = g.thread("loop")
            while not t.shutdown_requested:
                time.sleep(0.005)
            stopped.append(True)

        g.create("loop", worker)
        time.sleep(0.05)
        assert g.size() == 1
        g.request_shutdown_all()
        g.join_all(timeout_per_thread=2)
        assert stopped == [True]

    def test_duplicate_name_raises(self):
        g = ThreadGroup()
        ev = ManualEvent()
        g.create("x", ev.wait, 5)
        with pytest.raises(DMLCError, match="already running"):
            g.create("x", lambda: None)
        ev.signal()
        g.join_all()

    def test_manual_event(self):
        ev = ManualEvent()
        assert not ev.is_set()
        assert not ev.wait(0.01)
        ev.signal()
        assert ev.wait(0.01)
        ev.reset()
        assert not ev.is_set()


class TestBufferPool:
    def test_reuse(self):
        pool = BufferPool()
        a = pool.acquire(1000)
        assert len(a) == 1024  # size class
        pool.release(a)
        b = pool.acquire(900)
        assert b is a  # recycled
        assert pool.stats() == (1, 1)

    def test_distinct_classes(self):
        pool = BufferPool()
        a = pool.acquire(100)
        b = pool.acquire(10000)
        assert len(a) != len(b)

    def test_thread_local(self):
        assert thread_local_pool() is thread_local_pool()


class TestIndexedRecordIOWriter:
    def test_roundtrip_via_indexed_split(self, tmp_path, rng):
        data = tmp_path / "d.rec"
        records = [rng.bytes(rng.randint(1, 60)) for _ in range(40)]
        # make some records contain the magic (multi-frame + index offsets)
        records[5] = np.uint32(RECORDIO_MAGIC).tobytes() * 3
        with create_stream(str(data), "w") as ds, \
                create_stream(str(data) + ".idx", "w") as ix:
            w = IndexedRecordIOWriter(ds, ix)
            for r in records:
                w.write_record(r)
        split = InputSplit.create(str(data), 0, 1, "indexed_recordio")
        assert list(split) == records
        # sharded coverage at record granularity
        got = []
        for k in range(3):
            got.extend(InputSplit.create(str(data), k, 3,
                                         "indexed_recordio"))
        assert sorted(got) == sorted(records)

    def test_explicit_keys(self, tmp_path):
        data = tmp_path / "k.rec"
        with create_stream(str(data), "w") as ds, \
                create_stream(str(data) + ".idx", "w") as ix:
            w = IndexedRecordIOWriter(ds, ix)
            w.write_record(b"rec-a", key=100)
            w.write_record(b"rec-b", key=200)
        idx_text = (tmp_path / "k.rec.idx").read_text()
        assert idx_text.startswith("100\t0\n")
        split = InputSplit.create(str(data), 0, 1, "indexed_recordio")
        assert split.keys() == [100, 200]

    def test_shuffled_indexed_read(self, tmp_path, rng):
        data = tmp_path / "s.rec"
        records = [b"r%03d" % i for i in range(100)]
        with create_stream(str(data), "w") as ds, \
                create_stream(str(data) + ".idx", "w") as ix:
            w = IndexedRecordIOWriter(ds, ix)
            for r in records:
                w.write_record(r)
        split = InputSplit.create(str(data), 0, 1, "indexed_recordio",
                                  shuffle=True, seed=3, batch_size=10)
        e1 = list(split)
        e2 = list(split)
        assert sorted(e1) == records and sorted(e2) == records
        assert e1 != records  # actually shuffled
        assert e1 != e2       # epoch reshuffle


class TestStdinSplit:
    def test_stdin_records(self):
        code = (
            "import sys; sys.path.insert(0, '/root/repo')\n"
            "from dmlc_tpu.io.input_split import InputSplit\n"
            "s = InputSplit.create('-', 0, 1)\n"
            "print([r.decode() for r in s])\n")
        out = subprocess.run([sys.executable, "-c", code],
                             input=b"a\nbb\n\nccc\n", capture_output=True)
        assert out.returncode == 0, out.stderr.decode()
        assert "['a', 'bb', 'ccc']" in out.stdout.decode()


class TestClusterBackends:
    def test_mpi_command(self):
        line = mpi_command(4, ["python", "w.py"], "h:9")
        assert line.startswith("mpirun -n 4")
        assert "OMPI_COMM_WORLD_RANK" in line
        assert "DMLC_TPU_COORDINATOR_URI=h:9" in line

    def test_slurm_script(self):
        s = slurm_script(8, ["python", "w.py"], "h:9", partition="tpu")
        assert "#SBATCH --ntasks=8" in s
        assert "--partition=tpu" in s
        assert "SLURM_PROCID" in s

    def test_sge_script(self):
        s = sge_script(3, ["python", "w.py"], "h:9")
        assert "#$ -t 1-3" in s and "SGE_TASK_ID" in s

    def test_k8s_manifest(self):
        m = kubernetes_manifest(5, ["python", "w.py"], "h:9",
                                image="my/img:1")
        assert m["spec"]["completions"] == 5
        assert m["spec"]["completionMode"] == "Indexed"
        assert m["spec"]["template"]["spec"]["containers"][0][
            "image"] == "my/img:1"
        names = [e["name"] for e in
                 m["spec"]["template"]["spec"]["containers"][0]["env"]]
        assert "DMLC_TPU_COORDINATOR_URI" in names


class TestStdinRegressions:
    def test_recordio_on_stdin_raises(self):
        with pytest.raises(DMLCError, match="text"):
            InputSplit.create("-", 0, 1, "recordio")

    def test_sharded_stdin_raises(self):
        with pytest.raises(DMLCError, match="one part"):
            InputSplit.create("-", 1, 4)

    def test_streaming_chunks_bounded(self):
        # 3 MB piped through a 64 KB-chunk stdin split: many chunks,
        # records intact
        code = (
            "import sys; sys.path.insert(0, '/root/repo')\n"
            "from dmlc_tpu.io.input_split import InputSplit\n"
            "s = InputSplit.create('-', 0, 1, chunk_size=1)\n"  # floors 64KB
            "chunks = 0; recs = 0\n"
            "while True:\n"
            "    c = s.next_chunk()\n"
            "    if c is None: break\n"
            "    chunks += 1; recs += len(list(s.extract_records(c)))\n"
            "print(chunks, recs)\n")
        payload = b"".join(b"line-%06d\n" % i for i in range(200000))
        out = subprocess.run([sys.executable, "-c", code], input=payload,
                             capture_output=True)
        assert out.returncode == 0, out.stderr.decode()
        chunks, recs = map(int, out.stdout.split())
        assert recs == 200000
        assert chunks > 10  # streamed, not slurped


class TestBufferPoolRegression:
    def test_foreign_view_not_pooled(self):
        pool = BufferPool()
        a = pool.acquire(1024)
        view = a[:300]
        pool.release(view)  # dropped silently
        b = pool.acquire(300)
        assert len(b) == 512 and b is not view


class TestK8sTaskIdCompat:
    def test_both_task_id_names_injected(self):
        m = kubernetes_manifest(2, ["w"], "h:9", image="img")
        env = m["spec"]["template"]["spec"]["containers"][0]["env"]
        names = [e["name"] for e in env]
        assert "DMLC_TPU_TASK_ID" in names and "DMLC_TASK_ID" in names
