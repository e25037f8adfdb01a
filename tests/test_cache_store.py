"""On-disk sweep-cache store invariants (DESIGN.md §14): exact
round-trip, schema-version cold start, torn-write recovery, and
cross-process reuse through ``sweep.export_cache``/``import_cache``."""
import os

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import EvalOptions, GemmOp, Task, make_hw
from repro.core import sweep
from repro.core.ga import GAConfig
from repro.core.workload import uniform_partition
from repro.serve import cache_store
from repro.serve.cache_store import CacheStore


def toy_task(n=3, m=512):
    ops = [GemmOp("g0", M=m, K=256, N=512)]
    for i in range(1, n):
        ops.append(GemmOp(f"g{i}", M=m, K=ops[-1].N, N=512, chained=True))
    return Task(f"toy{n}_{m}", ops)


@pytest.fixture(autouse=True)
def _fresh_cache():
    sweep.clear_cache()
    yield
    sweep.clear_cache()


def _populated_cache():
    """Fill the process cache with one record of each family: eval
    (regime + flow), GA solver, pipelining."""
    task, hw = toy_task(), make_hw("A", 2, "hbm")
    pts = [sweep.EvalPoint(task, hw, EvalOptions(redistribution=True)),
           sweep.EvalPoint(task, hw, EvalOptions(congestion="flow"))]
    sweep.eval_sweep(pts)
    sweep.solve_grid(
        [sweep.EvalPoint(toy_task(2), make_hw("A", 2))], "latency",
        GAConfig(generations=3, population=16, patience=3, seed=1))
    sweep.pipeline_sweep(
        [sweep.PipelinePoint([("a", 1.0, 2.0, 1.0),
                              ("b", 0.5, 1.0, 0.5)], 4)])
    return sweep.export_cache()


def _assert_value_equal(a, b):
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            if isinstance(a[k], np.ndarray):
                np.testing.assert_array_equal(a[k], b[k])
            else:
                assert a[k] == b[k], k
        return
    # solver records: dataclasses with numpy fields
    assert type(a) is type(b)
    for f in vars(a) if hasattr(a, "__dict__") else ():
        va, vb = getattr(a, f), getattr(b, f)
        if isinstance(va, np.ndarray):
            np.testing.assert_array_equal(va, vb)
        elif hasattr(va, "Px"):          # Partition
            np.testing.assert_array_equal(va.Px, vb.Px)
            np.testing.assert_array_equal(va.Py, vb.Py)
            np.testing.assert_array_equal(va.collectors, vb.collectors)
        else:
            assert va == vb, f


def test_round_trip_exact(tmp_path):
    entries = _populated_cache()
    assert len(entries) >= 4
    store = CacheStore(tmp_path / "c.bin")
    store.save(entries)
    loaded = store.load()
    assert not store.last_load.cold_start
    assert not store.last_load.torn_tail
    assert set(loaded) == set(entries)
    for k in entries:
        _assert_value_equal(entries[k], loaded[k])


def test_append_accumulates(tmp_path):
    task, hw = toy_task(), make_hw("A", 2, "hbm")
    store = CacheStore(tmp_path / "c.bin")
    sweep.eval_sweep([sweep.EvalPoint(task, hw)])
    first = sweep.export_cache()
    store.append(first)                      # creates file + header
    sweep.eval_sweep(
        [sweep.EvalPoint(task, hw, EvalOptions(redistribution=True))])
    snap = sweep.export_cache()
    second = {k: v for k, v in snap.items() if k not in first}
    assert second
    store.append(second)
    loaded = store.load()
    assert set(loaded) == set(snap)


def test_schema_mismatch_cold_start(tmp_path, monkeypatch):
    entries = _populated_cache()
    store = CacheStore(tmp_path / "c.bin")
    monkeypatch.setattr(cache_store, "SCHEMA_VERSION", 999)
    store.save(entries)
    monkeypatch.undo()
    loaded = CacheStore(tmp_path / "c.bin").load()
    assert loaded == {}


def test_schema_mismatch_reports_reason(tmp_path, monkeypatch):
    entries = _populated_cache()
    path = tmp_path / "c.bin"
    monkeypatch.setattr(cache_store, "SCHEMA_VERSION", 999)
    CacheStore(path).save(entries)
    monkeypatch.undo()
    store = CacheStore(path)
    assert store.load() == {}
    assert store.last_load.cold_start
    assert "schema" in store.last_load.reason


def test_version_2_store_cold_starts(tmp_path, monkeypatch):
    """Stores written before ``GemmOp.group_sizes`` existed (schema 2)
    hold task fingerprints of another shape: they load as a cold start
    and say why."""
    assert cache_store.SCHEMA_VERSION == 3
    path = tmp_path / "v2.bin"
    monkeypatch.setattr(cache_store, "SCHEMA_VERSION", 2)
    CacheStore(path).save(_populated_cache())
    monkeypatch.undo()
    store = CacheStore(path)
    assert store.load() == {}
    assert store.last_load.cold_start
    assert store.last_load.reason.startswith("schema 2 != 3")


def test_missing_and_foreign_files_cold_start(tmp_path):
    store = CacheStore(tmp_path / "absent.bin")
    assert store.load() == {}
    assert store.last_load.cold_start

    junk = tmp_path / "junk.bin"
    junk.write_bytes(b"\x00\x01not a store" * 7)
    store = CacheStore(junk)
    assert store.load() == {}
    assert store.last_load.cold_start


def test_torn_write_recovery(tmp_path):
    entries = _populated_cache()
    path = tmp_path / "c.bin"
    store = CacheStore(path)
    store.save(entries)
    full = os.path.getsize(path)
    # Truncate mid-record (drop the last 7 bytes): the tail record is
    # torn, every earlier record must survive intact.
    with open(path, "r+b") as f:
        f.truncate(full - 7)
    loaded = CacheStore(path).load()
    st2 = CacheStore(path)
    loaded = st2.load()
    assert st2.last_load.torn_tail
    assert not st2.last_load.cold_start
    assert 0 < len(loaded) < len(entries)
    for k, v in loaded.items():
        _assert_value_equal(entries[k], v)
    # Appending after recovery-by-load still works on a fresh save.
    st2.save(entries)
    assert set(CacheStore(path).load()) == set(entries)


def test_torn_header_cold_start(tmp_path):
    entries = _populated_cache()
    path = tmp_path / "c.bin"
    CacheStore(path).save(entries)
    with open(path, "r+b") as f:
        f.truncate(5)                    # inside the header record
    store = CacheStore(path)
    assert store.load() == {}
    assert store.last_load.cold_start


def test_corrupt_record_checksum_drops_tail(tmp_path):
    entries = _populated_cache()
    path = tmp_path / "c.bin"
    CacheStore(path).save(entries)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:         # flip one byte near the end
        f.seek(size - 3)
        b = f.read(1)
        f.seek(size - 3)
        f.write(bytes([b[0] ^ 0xFF]))
    st2 = CacheStore(path)
    loaded = st2.load()
    assert st2.last_load.torn_tail
    assert len(loaded) < len(entries)
    for k, v in loaded.items():
        _assert_value_equal(entries[k], v)


def test_cross_process_reuse_two_sequential_loads(tmp_path):
    """Process A computes + persists; processes B and C (fresh caches)
    both serve the same points entirely from the store."""
    task, hw = toy_task(), make_hw("A", 2, "hbm")
    pts = [sweep.EvalPoint(task, hw),
           sweep.EvalPoint(task, hw, EvalOptions(redistribution=True))]
    ref = sweep.eval_sweep(pts)
    CacheStore(tmp_path / "c.bin").save(sweep.export_cache())

    for _process in ("B", "C"):
        sweep.clear_cache()
        n = sweep.import_cache(CacheStore(tmp_path / "c.bin").load())
        assert n == len(pts)
        recs = sweep.eval_sweep(pts)
        stats = sweep.cache_stats()
        assert stats["misses"] == 0 and stats["hits"] == len(pts)
        for a, b in zip(ref, recs):
            assert a["latency"] == b["latency"]       # bit-identical
            np.testing.assert_array_equal(a["t_in"], b["t_in"])


def test_import_cache_existing_keys_win():
    task, hw = toy_task(), make_hw("A", 2, "hbm")
    pt = sweep.EvalPoint(task, hw)
    rec = sweep.eval_sweep([pt])[0]
    snap = sweep.export_cache()
    (k, v), = snap.items()
    poisoned = dict(v, latency=-1.0)
    assert sweep.import_cache({k: poisoned}) == 0     # resident wins
    assert sweep.eval_sweep([pt])[0]["latency"] == rec["latency"]
    assert sweep.import_cache({k: poisoned}, replace=True) == 1
    assert sweep.eval_sweep([pt])[0]["latency"] == -1.0


# ------------------------------------------------- property-based store
_key_atom = st.one_of(
    st.text(max_size=8),
    st.integers(min_value=-2**40, max_value=2**40),
    st.floats(allow_nan=False, allow_infinity=False),
    st.binary(max_size=16),
    st.booleans(),
)


@settings(max_examples=20, deadline=None)
@given(st.dictionaries(
    st.tuples(_key_atom, _key_atom, _key_atom),
    st.fixed_dictionaries({
        "latency": st.floats(allow_nan=False, allow_infinity=False),
        "arr": st.lists(st.floats(allow_nan=False, allow_infinity=False),
                        max_size=8),
    }),
    max_size=8,
))
def test_store_roundtrip_random_fingerprint_axes(tmp_path_factory, entries):
    """Any pickle-able fingerprint tuple and record dict round-trips the
    store exactly (the axes of PRs 1–5 are tuples of exactly these atom
    types plus frozen dataclasses)."""
    entries = {k: dict(v, arr=np.asarray(v["arr"])) for k, v in
               entries.items()}
    path = tmp_path_factory.mktemp("store") / "c.bin"
    store = CacheStore(path)
    store.save(entries)
    loaded = store.load()
    assert set(loaded) == set(entries)
    for k in entries:
        assert loaded[k]["latency"] == entries[k]["latency"]
        np.testing.assert_array_equal(loaded[k]["arr"], entries[k]["arr"])
