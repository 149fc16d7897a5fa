"""Pivot-cache satellites: EF commit-delta codec edge cases and
``PivotStore._make_room`` spill-policy hardening.

The codec is load-bearing for the distributed bit-identity contract —
replicas install exactly what decode returns — so every boundary the
encoder can reach (empty deltas, single pivots, the raw-fallback key
range, duplicate commits, arbitrary record slices) must round-trip
losslessly.  ``_make_room`` is one-way (demotion drops explicit R keys),
so its order and refusal behaviour must be deterministic.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analyze import sanitizing
from repro.core.payload import pack_column_payload, unpack_column_payload
from repro.core.pivot_cache import (PackedPivotCache, decode_commit_delta,
                                    encode_commit_delta)
from repro.core.reduction import PivotStore


def _records_equal(sent, got):
    assert len(sent) == len(got)
    for a, b in zip(sent, got):
        assert int(a["low"]) == int(b["low"])
        assert int(a["col_id"]) == int(b["col_id"])
        assert a["mode"] == b["mode"]
        if a["mode"] == "explicit":
            assert np.array_equal(np.asarray(a["column"]), b["column"])
        else:
            assert b["column"] is None
        sent_gens = (np.sort(np.asarray(a["gens"], dtype=np.int64))
                     if a.get("gens") is not None
                     else np.zeros(0, dtype=np.int64))
        assert np.array_equal(sent_gens, b["gens"])


def _record(rng, low, col_id, max_key=10_000):
    mode = "explicit" if rng.integers(2) else "implicit"
    n_col = int(rng.integers(0, 9))
    column = np.sort(rng.choice(max_key, size=n_col, replace=False)
                     ).astype(np.int64)
    gens = rng.integers(0, max_key, size=int(rng.integers(0, 5))
                        ).astype(np.int64)
    return {"low": low, "col_id": col_id, "mode": mode,
            "column": column if mode == "explicit" else None, "gens": gens}


# ---------------------------------------------------------------------------
# EF commit-delta codec edge cases
# ---------------------------------------------------------------------------

def test_delta_empty_set():
    payload = encode_commit_delta([])
    assert decode_commit_delta(payload) == []


def test_delta_single_pivot():
    records = [{"low": 42, "col_id": 7, "mode": "explicit",
                "column": np.array([42, 99], dtype=np.int64),
                "gens": np.array([3], dtype=np.int64)}]
    _records_equal(records, decode_commit_delta(encode_commit_delta(records)))


def test_delta_empty_column_and_gens():
    records = [{"low": 1, "col_id": 2, "mode": "explicit",
                "column": np.zeros(0, dtype=np.int64), "gens": None},
               {"low": 3, "col_id": 4, "mode": "implicit",
                "column": None, "gens": np.zeros(0, dtype=np.int64)}]
    _records_equal(records, decode_commit_delta(encode_commit_delta(records)))


@pytest.mark.parametrize("columns", [
    [],
    [np.zeros(0, dtype=np.int64)],
    [np.array([7], dtype=np.int64)],
    [np.sort(np.random.default_rng(3).choice(10**9, size=10_000,
                                             replace=False))],
    [np.array([2**63 - 3, 2**63 - 2, 2**63 - 1], dtype=np.int64)],
], ids=["no-columns", "empty-column", "one-key", "10000-keys",
        "keys-near-2^63"])
def test_column_payload_roundtrip(columns):
    """The Elias–Fano column codec under the delta round-trips every
    boundary it can reach, the raw fallback past 2**62 included."""
    back = unpack_column_payload(pack_column_payload(columns))
    assert len(back) == len(columns)
    for sent, got in zip(columns, back):
        assert got.dtype == np.int64
        np.testing.assert_array_equal(got, sent)


def test_delta_max_key_boundary_takes_raw_fallback():
    """Keys near 2**62 overflow the EF column embedding (``U * ncols``),
    forcing the raw body encoding — which must round-trip identically."""
    big = 2**62 - 3
    records = [{"low": big, "col_id": big - 1, "mode": "explicit",
                "column": np.array([big - 5, big], dtype=np.int64),
                "gens": np.array([0, big - 7], dtype=np.int64)},
               {"low": 5, "col_id": 6, "mode": "implicit",
                "column": None, "gens": np.array([big], dtype=np.int64)}]
    payload = encode_commit_delta(records)
    _records_equal(records, decode_commit_delta(payload))


def test_delta_sanitized_encode_is_clean():
    rng = np.random.default_rng(7)
    records = [_record(rng, low=int(l), col_id=i)
               for i, l in enumerate(rng.choice(5000, 20, replace=False))]
    with sanitizing(True):
        _records_equal(records,
                       decode_commit_delta(encode_commit_delta(records)))


@settings(deadline=None, max_examples=25)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(0, 12),
       start=st.integers(0, 12), stop=st.integers(0, 12))
def test_delta_roundtrip_under_random_slices(seed, n, start, stop):
    rng = np.random.default_rng(seed)
    lows = rng.choice(100_000, size=n, replace=False)
    records = [_record(rng, low=int(l), col_id=int(rng.integers(1_000_000)))
               for l in lows]
    subset = records[min(start, stop):max(start, stop)]
    _records_equal(subset, decode_commit_delta(encode_commit_delta(subset)))


def test_put_column_duplicate_commit_idempotent():
    """Committing the same low twice counts the call but stores one copy."""
    cache = PackedPivotCache()
    keys = np.array([3, 8, 11], dtype=np.int64)
    cache.put_column(5, keys)
    first_bytes = cache.column_bytes
    cache.put_column(5, np.array([999], dtype=np.int64))   # dup: ignored
    assert cache.n_materializations == 2
    assert cache.column_bytes == first_bytes
    assert np.array_equal(cache.get_column(5), keys)
    assert cache.n_mat_hits == 1


# ---------------------------------------------------------------------------
# PivotStore._make_room hardening
# ---------------------------------------------------------------------------

class _NoAdapter:
    """Commit/_make_room never touch the adapter with the sanitizer off."""

    def __getattr__(self, name):
        raise AssertionError(f"adapter.{name} touched by spill bookkeeping")


def _store(budget):
    return PivotStore(_NoAdapter(), "explicit", store_budget_bytes=budget)


def _commit(store, low, n_keys):
    r = np.arange(low, low + n_keys, dtype=np.int64)
    store.commit(low, low + 1, r, np.zeros(0, dtype=np.int64), trivial=False)


def test_make_room_demotes_oldest_on_equal_sizes():
    """Equal-size heap entries tie-break on index: oldest demoted first,
    deterministically — the spill order is part of the perf contract."""
    with sanitizing(False):
        store = _store(budget=48)
        _commit(store, 100, 3)       # idx 0: 24 bytes
        _commit(store, 200, 3)       # idx 1: 24 bytes
        assert store.col_modes == ["explicit", "explicit"]
        _commit(store, 300, 2)       # 16 bytes: must demote exactly idx 0
        assert store.col_modes == ["implicit", "explicit", "explicit"]
        _commit(store, 400, 2)       # next tie pops idx 1
        assert store.col_modes == ["implicit", "implicit",
                                   "explicit", "explicit"]
        assert store.n_spilled == 2
        assert store.bytes_stored <= 48


def test_make_room_zero_budget_degrades_to_all_implicit():
    with sanitizing(False):
        store = _store(budget=0)
        for i, low in enumerate((10, 20, 30)):
            _commit(store, low, n_keys=i + 1)    # must not raise
        assert store.col_modes == ["implicit"] * 3
        assert store.n_spilled == 3
        # implicit columns hold the (empty) gens, not the R keys
        assert store.bytes_stored == 0


def test_make_room_refuses_when_incoming_is_biggest():
    """An incoming column at least as big as every stored explicit column
    spills itself; nothing already stored is demoted for it."""
    with sanitizing(False):
        store = _store(budget=48)
        _commit(store, 100, 3)
        _commit(store, 200, 3)
        _commit(store, 300, 3)       # 24 bytes == heap max: refuses
        assert store.col_modes == ["explicit", "explicit", "implicit"]
        assert store.n_spilled == 1


def test_make_room_rolls_back_doomed_demotion_plan():
    """When demoting everything still cannot fit the incoming column, no
    planned demotion may be applied — demotion is one-way."""
    with sanitizing(False):
        store = _store(budget=48)
        _commit(store, 100, 3)
        _commit(store, 200, 3)
        # incoming: r = 16 bytes but 48 bytes of tracked gens -> total 64
        # never fits even after demoting both stored columns
        r = np.array([900, 901], dtype=np.int64)
        gens = np.arange(6, dtype=np.int64)
        store.commit(900, 901, r, gens, trivial=False)
        assert store.col_modes == ["explicit", "explicit", "implicit"]
        assert store.n_spilled == 1
        assert len(store._explicit_heap) == 2    # plan fully rolled back
