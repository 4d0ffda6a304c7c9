"""AOT trace cache (core/trace_cache): cross-process trace skipping.

A fresh process on a warm cache must produce a bit-identical model by
DESERIALIZING the exported program instead of re-tracing; the key must
invalidate on config and source changes.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mmlspark_tpu.engine.booster as bo
from mmlspark_tpu.core import trace_cache as tc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train_once(monkeypatch, tmp_path, cache_dir):
    monkeypatch.delenv("MMLSPARK_TPU_NO_TRACE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    monkeypatch.setattr(bo, "_TRACE_CACHE_MIN_WORK", 0)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    b = bo.train(dict(objective="binary", num_iterations=4, num_leaves=7,
                      min_data_in_leaf=2, max_bin=31),
                 bo.Dataset(X, y))
    return b.predict(X)


def test_export_written_and_replayed(monkeypatch, tmp_path):
    cache = tmp_path / "traces"
    p1 = _train_once(monkeypatch, tmp_path, cache)
    blobs = list(cache.glob("*.jaxexp"))
    assert blobs, "no exported program written"
    # memo cleared → the next fit must REPLAY the blob (same inode: a
    # re-export goes through tmp+rename and would replace the file)
    tc._EXP_MEMO.clear()
    before = {b: b.stat().st_ino for b in blobs}
    p2 = _train_once(monkeypatch, tmp_path, cache)
    np.testing.assert_array_equal(p1, p2)
    after = {b: b.stat().st_ino for b in cache.glob("*.jaxexp")}
    assert before == after  # replayed, not re-exported


def test_key_invalidates_on_config_change(monkeypatch, tmp_path):
    cache = tmp_path / "traces"
    _train_once(monkeypatch, tmp_path, cache)
    n1 = len(list(cache.glob("*.jaxexp")))
    # different num_leaves → different program → new blob
    monkeypatch.setattr(bo, "_TRACE_CACHE_MIN_WORK", 0)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    bo.train(dict(objective="binary", num_iterations=4, num_leaves=15,
                  min_data_in_leaf=2, max_bin=31), bo.Dataset(X, y))
    assert len(list(cache.glob("*.jaxexp"))) > n1


def test_source_hash_covers_engine(monkeypatch):
    h1 = tc._source_hash()
    assert isinstance(h1, str) and len(h1) == 64
    # deterministic within a process
    assert tc._source_hash() == h1


def test_fresh_process_replays_without_retracing(tmp_path):
    """The actual contract: process 2 loads process 1's blob and trains
    bit-identically (subprocess so nothing is memoized)."""
    cache = tmp_path / "traces"
    script = tmp_path / "w.py"
    script.write_text(textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import mmlspark_tpu.engine.booster as bo
        bo._TRACE_CACHE_MIN_WORK = 0
        rng = np.random.default_rng(0)
        X = rng.normal(size=(512, 4))
        y = (X[:, 0] > 0).astype(np.float64)
        b = bo.train(dict(objective="binary", num_iterations=4,
                          num_leaves=7, min_data_in_leaf=2, max_bin=31),
                     bo.Dataset(X, y))
        print(json.dumps({{"p": b.predict(X)[:8].tolist()}}))
    """))
    env = {"PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu", "PYTHONDONTWRITEBYTECODE": "1",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "MMLSPARK_TPU_NO_COMPILE_CACHE": "1"}
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, str(script)], env=env,
                           capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1])["p"])
    assert list(cache.glob("*.jaxexp"))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_opt_out(monkeypatch, tmp_path):
    monkeypatch.setenv("MMLSPARK_TPU_NO_TRACE_CACHE", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "t2"))
    monkeypatch.setattr(bo, "_TRACE_CACHE_MIN_WORK", 0)
    rng = np.random.default_rng(1)
    X = rng.normal(size=(256, 3))
    y = (X[:, 0] > 0).astype(np.float64)
    bo.train(dict(objective="binary", num_iterations=2, num_leaves=4,
                  min_data_in_leaf=2, max_bin=15), bo.Dataset(X, y))
    assert not (tmp_path / "t2").exists()


def test_mesh_program_exports_and_replays(monkeypatch, tmp_path):
    """r5 (r4 verdict next #1): SHARDED programs ride the trace cache too —
    a data-parallel mesh fit writes an exported program, and a fresh memo
    replays the blob bit-identically."""
    cache = tmp_path / "traces_mesh"
    monkeypatch.delenv("MMLSPARK_TPU_NO_TRACE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    monkeypatch.setattr(bo, "_TRACE_CACHE_MIN_WORK", 0)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1024, 6))
    y = (X[:, 0] - 0.3 * X[:, 1] > 0).astype(np.float64)
    params = dict(objective="binary", num_iterations=4, num_leaves=7,
                  min_data_in_leaf=2, max_bin=31, tree_learner="data")
    b1 = bo.train(params, bo.Dataset(X, y))
    p1 = b1.predict(X)
    blobs = list(cache.glob("*.jaxexp"))
    assert blobs, "no exported program written for the mesh path"
    tc._EXP_MEMO.clear()
    before = {b: b.stat().st_ino for b in blobs}
    p2 = bo.train(params, bo.Dataset(X, y)).predict(X)
    np.testing.assert_array_equal(p1, p2)
    after = {b: b.stat().st_ino for b in cache.glob("*.jaxexp")}
    assert before == after  # replayed, not re-exported


def test_mesh_key_separates_topologies(monkeypatch, tmp_path):
    # meshless and mesh programs must never share a blob
    from mmlspark_tpu.core.trace_cache import mesh_trace_key
    from mmlspark_tpu.parallel.mesh import default_mesh

    assert mesh_trace_key(None) == "meshless"
    k8 = mesh_trace_key(default_mesh())
    k4 = mesh_trace_key(default_mesh(num_devices=4))
    assert k8 != k4 != "meshless"


_PL_TRACE_WORKER = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    from mmlspark_tpu.spark_bridge import barrier_context_from_task_infos
    from mmlspark_tpu.parallel.distributed import (
        global_mesh, initialize_distributed,
    )
    import mmlspark_tpu.engine.booster as bo
    from mmlspark_tpu.ops.binning import distributed_fit

    bo._TRACE_CACHE_MIN_WORK = 0
    pid = int(sys.argv[1]); port = sys.argv[2]

    def partition(p):
        rng = np.random.default_rng(500 + p)
        n = 400 + 11 * p
        X = rng.normal(size=(n, 5))
        y = (X[:, 0] - 0.4 * X[:, 1]
             + rng.normal(scale=0.3, size=n) > 0).astype(np.float64)
        return X, y

    ctx = barrier_context_from_task_infos(
        ["127.0.0.1:" + port, "127.0.0.1:0"], pid,
        coordinator_port=int(port))
    initialize_distributed(ctx)
    X, y = partition(pid)
    bm = distributed_fit(X, max_bin=31)
    b = bo.train(dict(objective="binary", num_iterations=4, num_leaves=7,
                      min_data_in_leaf=2, tree_learner="data"),
                 bo.Dataset(X, y), bin_mapper=bm,
                 mesh=global_mesh(), process_local=True)
    print(json.dumps({{"pid": pid, "model": b.save_model_string()}}))
""")


@pytest.mark.slow
def test_process_local_trace_cache_two_process_bit_identity(tmp_path):
    """The multi-controller leg of the r5 contract: a 2-process
    process_local run exports its sharded program; a SECOND 2-process run
    (fresh processes, warm cache) replays the blobs and produces the
    bit-identical model on both processes."""
    import socket

    cache = tmp_path / "traces_pl"
    script = tmp_path / "w_pl.py"
    script.write_text(_PL_TRACE_WORKER.format(repo=REPO))
    base_env = {"PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/root",
                "JAX_PLATFORMS": "cpu", "PYTHONDONTWRITEBYTECODE": "1",
                "JAX_COMPILATION_CACHE_DIR": str(cache),
                "MMLSPARK_TPU_NO_COMPILE_CACHE": "1"}
    models = []
    inodes = []
    for round_i in range(2):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(pid), str(port)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env=base_env,
            )
            for pid in range(2)
        ]
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
            outs.append(json.loads(out.strip().splitlines()[-1]))
        assert outs[0]["model"] == outs[1]["model"]  # SPMD replication
        models.append(outs[0]["model"])
        blobs = sorted(cache.glob("*.jaxexp"))
        assert blobs, "no exported sharded program written"
        inodes.append({b: b.stat().st_ino for b in blobs})
    # warm round replayed the same blobs (no re-export) and trained the
    # bit-identical model
    assert models[0] == models[1]
    assert inodes[0] == inodes[1]
