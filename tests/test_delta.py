"""Delta checkpoints: tile digests, delta frames, chain compose, tiering.

The load-bearing property: base + N delta frames restores a state
BIT-EXACTLY equal to a full snapshot — across dtype-boundary leaves
(bf16/f16/i8), partial trailing tiles, scalars and empties — enforced
both at the serde layer and through FileCheckpointer's manifest-verified
composed loads.
"""
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.checkpoint import FileCheckpointer, serde
from repro.checkpoint.manifest import tree_digest
from repro.checkpoint.memory_ckpt import BuddyStore
from repro.kernels.checksum.ref import (TILE_BYTES, checksum_words_ref,
                                        scalar_from_tiles,
                                        tile_checksums_ref)

BF16 = np.dtype(ml_dtypes.bfloat16)


def _bit_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (str(a.dtype) == str(b.dtype) and a.shape == b.shape
            and np.ascontiguousarray(a).reshape(-1).view(np.uint8).tobytes()
            == np.ascontiguousarray(b).reshape(-1).view(np.uint8).tobytes())


# ------------------------------------------------------------ tile digests

def test_tile_digests_fold_to_scalar_checksum():
    rng = np.random.default_rng(3)
    for arr in [rng.standard_normal(5).astype(np.float32),
                rng.standard_normal(TILE_BYTES // 4).astype(np.float32),
                rng.standard_normal(TILE_BYTES // 4 + 1).astype(np.float32),
                rng.standard_normal(3000).astype(BF16),
                rng.integers(0, 255, 3 * TILE_BYTES + 7).astype(np.uint8),
                np.zeros((0,), np.float32),
                np.float64(2.5).reshape(())]:
        tiles = tile_checksums_ref(arr)
        assert scalar_from_tiles(tiles) == checksum_words_ref(arr)


def test_tile_digest_localizes_change():
    a = np.zeros(4 * TILE_BYTES // 4, np.float32)     # 4 exact tiles
    b = a.copy()
    b[TILE_BYTES // 4 + 3] = 1.0                      # dirty tile 1 only
    ta, tb = tile_checksums_ref(a), tile_checksums_ref(b)
    changed = np.any(ta != tb, axis=1)
    assert list(changed) == [False, True, False, False]


@pytest.mark.parametrize("factor", [0.5, 2.0])
def test_tile_digest_sees_uniform_exponent_shift(factor):
    # one tile of floats in [1, 2): every word shares exponent 127, and
    # exactly half have mantissa bit 7 set, the case where one
    # xor-shift-multiply round cancels a halving to the same digest
    rng = np.random.default_rng(7)
    n = TILE_BYTES // 4
    mant = rng.integers(0, 1 << 23, n, dtype=np.uint32) & ~np.uint32(1 << 7)
    mant[rng.permutation(n)[:n // 2]] |= np.uint32(1 << 7)
    a = (np.uint32(0x3F800000) | mant).view(np.float32)
    b = (a * np.float32(factor)).astype(np.float32)
    assert np.any(tile_checksums_ref(a) != tile_checksums_ref(b))


def test_tile_digest_device_parity():
    from repro.kernels.checksum.ops import tile_checksums
    rng = np.random.default_rng(5)
    for arr in [rng.standard_normal(2048).astype(np.float32),
                rng.standard_normal(513).astype(np.float16)]:
        assert np.array_equal(tile_checksums(jnp.asarray(arr)),
                              tile_checksums_ref(arr))


def test_tile_digest_pallas_interpret_parity():
    from repro.kernels.checksum.kernel import tile_checksum_kernel
    from repro.kernels.checksum.ops import _device_words
    rng = np.random.default_rng(6)
    arr = rng.standard_normal(3 * TILE_BYTES // 4 + 11).astype(np.float32)
    words = _device_words(jnp.asarray(arr))
    got = np.asarray(tile_checksum_kernel(words, interpret=True))
    assert np.array_equal(got, tile_checksums_ref(arr))


# ------------------------------------------------------------ serde deltas

def _mutate(flat, rng, n_edits=3):
    """Randomly mutate a few scattered elements of a few leaves."""
    out = {k: np.array(v) for k, v in flat.items()}
    keys = [k for k in out if out[k].size]
    for k in rng.choice(keys, size=min(n_edits, len(keys)),
                        replace=False) if keys else []:
        v = out[k].reshape(-1)
        idx = rng.integers(0, v.size)
        v[idx] = v[idx] + np.asarray(1, dtype=v.dtype) \
            if v.dtype != np.bool_ else ~v[idx]
    return out


@st.composite
def boundary_leaves(draw):
    dtype = draw(st.sampled_from(
        [np.float32, np.float16, np.int8, np.uint64, BF16]))
    # sizes straddling word/tile boundaries, incl. partial trailing tiles
    n = draw(st.sampled_from(
        [0, 1, 3, 7, TILE_BYTES // 4 - 1, TILE_BYTES // 4,
         TILE_BYTES // 4 + 1, 2 * TILE_BYTES // 4 + 13]))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    return rng.standard_normal(n).astype(dtype)


@given(st.dictionaries(st.text(alphabet="abcd", min_size=1, max_size=4),
                       boundary_leaves(), min_size=1, max_size=5),
       st.integers(1, 4), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_base_plus_n_deltas_bit_exact(flat, n_deltas, seed):
    """base + N chained delta frames == the full snapshot, bit for bit."""
    rng = np.random.default_rng(seed)
    frames = {0: serde.to_bytes(flat, {"step": 0})}
    tiles = serde.tile_digests(flat)
    cur = flat
    for step in range(1, n_deltas + 1):
        cur = _mutate(cur, rng)
        plan = serde.delta_plan(cur, tiles)
        frames[step] = serde.to_delta_bytes(cur, plan, base_step=step - 1,
                                            extra={"step": step})
        tiles = plan.new_tiles
    assert serde.composable_steps(frames) == list(range(n_deltas + 1))
    extra, got = serde.compose(frames, n_deltas)
    assert extra == {"step": n_deltas}
    want = serde.from_bytes(serde.to_bytes(cur))[1]   # full-snapshot oracle
    assert set(got) == set(want)
    for k in want:
        assert _bit_equal(want[k], got[k]), k


def test_delta_plan_marks_new_and_reshaped_leaves_full():
    a = {"x": np.arange(100, dtype=np.float32)}
    tiles = serde.tile_digests(a)
    b = {"x": np.arange(50, dtype=np.float32),       # reshaped
         "y": np.ones(10, np.float32)}               # new
    plan = serde.delta_plan(b, tiles)
    assert plan.entries["x"] is None and plan.entries["y"] is None


def test_delta_plan_marks_same_bytes_reshape_full():
    """Identical bytes under a different shape/dtype must not be treated
    as a clean leaf — the composed state would keep the stale shape."""
    a = {"x": np.arange(1024, dtype=np.float32).reshape(2, 512)}
    tiles = serde.tile_digests(a)
    b = {"x": np.asarray(a["x"]).reshape(1024)}        # same bytes
    plan = serde.delta_plan(b, tiles)
    assert plan.entries["x"] is None                   # full leaf
    c = {"x": np.asarray(a["x"]).view(np.int32)}       # same bytes, recast
    plan = serde.delta_plan(c, tiles)
    assert plan.entries["x"] is None


def test_delta_plan_infeasible_on_removed_leaf():
    a = {"x": np.ones(4, np.float32), "y": np.ones(4, np.float32)}
    tiles = serde.tile_digests(a)
    plan = serde.delta_plan({"x": np.ones(4, np.float32)}, tiles)
    assert not plan.feasible and plan.dirty_fraction == 1.0


def test_clean_snapshot_delta_is_header_only():
    flat = {"x": np.arange(5000, dtype=np.float32)}
    tiles = serde.tile_digests(flat)
    plan = serde.delta_plan(flat, tiles)
    buf = serde.to_delta_bytes(flat, plan, base_step=1)
    assert len(buf) < 256
    _, _, out = serde.apply_delta(serde.from_bytes(
        serde.to_bytes(flat))[1], buf)
    assert _bit_equal(out["x"], flat["x"])


def test_broken_chain_not_composable():
    flat = {"x": np.arange(64, dtype=np.float32)}
    tiles = serde.tile_digests(flat)
    plan = serde.delta_plan(flat, tiles)
    d = serde.to_delta_bytes(flat, plan, base_step=1)
    assert serde.composable_steps({2: d}) == []
    with pytest.raises(KeyError):
        serde.compose({2: d}, 2)


@st.composite
def dirty_mask_edits(draw):
    """A random sparse edit plan: (leaf_index, start_frac, run_len) runs
    to dirty — exercises arbitrary tile masks, not just single elements."""
    n_runs = draw(st.integers(0, 4))
    return [(draw(st.integers(0, 7)),
             draw(st.floats(0.0, 1.0)),
             draw(st.integers(1, 600)))
            for _ in range(n_runs)]


def _apply_edits(flat, edits, rng):
    out = {k: np.array(v) for k, v in flat.items()}
    keys = sorted(out)
    for leaf_i, start_frac, run in edits:
        k = keys[leaf_i % len(keys)]
        v = out[k].reshape(-1)
        if not v.size:
            continue
        lo = int(start_frac * (v.size - 1))
        hi = min(v.size, lo + run)
        v[lo:hi] = rng.standard_normal(hi - lo).astype(v.dtype) \
            if v.dtype != np.bool_ else ~v[lo:hi]
    return out


def _check_dirty_mask_chains(seed, retain, edit_plans):
    """Random dirty masks x random chain lengths: every frame the
    retention window keeps must compose bit-exactly, and the window's
    chain walk must never reference a pruned (GC'd) base — the
    BuddyStore-prune + composable_steps contract under arbitrary
    dirtiness."""
    rng = np.random.default_rng(seed)
    flat = {"a": rng.standard_normal(2500).astype(np.float32),
            "b": rng.standard_normal(700).astype(BF16),
            "c": rng.integers(0, 255, 3 * TILE_BYTES + 7).astype(np.uint8)}
    store = BuddyStore(0, 2, retain=retain)
    store.save(1, serde.to_bytes(flat, {"step": 1}))
    tiles = serde.tile_digests(flat)
    oracle = {1: flat}
    cur = flat
    for i, edits in enumerate(edit_plans):
        step = i + 2
        cur = _apply_edits(cur, edits, rng)
        plan = serde.delta_plan(cur, tiles)
        if plan.feasible and i % 3 != 2:          # random-ish chain breaks
            frame = serde.to_delta_bytes(cur, plan, base_step=step - 1,
                                         extra={"step": step})
        else:
            frame = serde.to_bytes(cur, {"step": step})
        store.save(step, frame)
        tiles = plan.new_tiles
        oracle[step] = cur
        held = store.local_map()
        comp = serde.composable_steps(held)
        # the newest step always composes, and nothing composable chains
        # through a pruned frame (chain_steps would KeyError -> excluded)
        assert step in comp
        for s in comp:
            assert set(serde.chain_steps(held, s)) <= set(held)
            extra, got = serde.compose(held, s)
            assert extra["step"] == s
            for k in oracle[s]:
                assert _bit_equal(got[k], oracle[s][k]), (s, k)


@given(st.integers(0, 2**31 - 1), st.integers(1, 6),
       st.lists(dirty_mask_edits(), min_size=1, max_size=6))
@settings(max_examples=20, deadline=None)
def test_random_dirty_masks_compose_bit_exact(seed, retain, edit_plans):
    _check_dirty_mask_chains(seed, retain, edit_plans)


def test_random_dirty_masks_compose_bit_exact_seeded():
    """Deterministic replay of the property above for environments
    without hypothesis — same invariant, pre-drawn plans."""
    for seed in (0, 7, 1234):
        rng = np.random.default_rng(seed ^ 0x5EED)
        plans = [[(int(rng.integers(0, 8)), float(rng.uniform()),
                   int(rng.integers(1, 600)))
                  for _ in range(rng.integers(0, 5))]
                 for _ in range(rng.integers(1, 7))]
        _check_dirty_mask_chains(seed, int(rng.integers(1, 7)), plans)


def _check_file_ckpt_chains(seed, delta_every, keep, n_saves):
    """FileCheckpointer under random dirtiness and chain lengths: every
    committed step loads bit-exactly and the GC'd directory still
    contains every base its surviving delta chains reference."""
    import tempfile
    from repro.checkpoint.manifest import tree_digest as td
    rng = np.random.default_rng(seed)
    d = tempfile.mkdtemp()
    try:
        ck = FileCheckpointer(d, keep=keep, n_shards=2,
                              delta_every=delta_every)
        state = {"w": rng.standard_normal(20000).astype(np.float32),
                 "b": rng.standard_normal(300).astype(np.float32)}
        digests = {}
        for step in range(1, n_saves + 1):
            state = {k: np.array(v) for k, v in state.items()}
            frac = rng.uniform(0.001, 0.9)        # sometimes > max_dirty
            n = max(1, int(frac * state["w"].size))
            lo = rng.integers(0, state["w"].size - n + 1)
            state["w"][lo:lo + n] += 1.0
            ck.save(step, state)
            digests[step] = td(state)
        steps = ck.steps()
        assert steps[-1] == n_saves
        # chain closure of everything kept is fully on disk
        assert ck._chain_closure(steps) <= set(steps)
        for s in steps:
            _, loaded = ck.load(s)
            assert td(loaded) == digests[s], s
    finally:
        import shutil
        shutil.rmtree(d, ignore_errors=True)


@given(st.integers(0, 2**31 - 1), st.integers(2, 5), st.integers(2, 4),
       st.integers(4, 9))
@settings(max_examples=10, deadline=None)
def test_file_ckpt_random_chains_never_lose_anchor(seed, delta_every,
                                                   keep, n_saves):
    _check_file_ckpt_chains(seed, delta_every, keep, n_saves)


def test_file_ckpt_random_chains_never_lose_anchor_seeded():
    for seed, de, keep, n in [(1, 2, 2, 6), (2, 3, 2, 8), (3, 4, 3, 9),
                              (4, 5, 4, 7)]:
        _check_file_ckpt_chains(seed, de, keep, n)


# --------------------------------------------------------- FileCheckpointer

def test_file_ckpt_delta_chain_roundtrip(tmp_path):
    ck = FileCheckpointer(str(tmp_path), keep=4, n_shards=3, delta_every=4)
    rng = np.random.default_rng(0)
    state = {"a": rng.standard_normal(30000).astype(np.float32),
             "nest": {"b": rng.standard_normal((64, 9)).astype(np.float32)},
             "step": np.int32(0)}
    digests = {}
    for step in range(1, 7):
        state = {"a": np.array(state["a"]),
                 "nest": {"b": np.array(state["nest"]["b"])},
                 "step": np.int32(step)}
        state["a"][step * 31:step * 31 + 40] += 1.0
        ck.save(step, state)
        digests[step] = tree_digest(state)
        kind = ck._manifest(step).kind
        assert kind == ("full" if step in (1, 5) else "delta"), step
    for step in ck.steps():
        man, loaded = ck.load(step)
        assert tree_digest(loaded) == digests[step], step


def test_file_ckpt_gc_keeps_chain_anchor(tmp_path):
    ck = FileCheckpointer(str(tmp_path), keep=2, n_shards=1, delta_every=4)
    state = {"w": np.arange(20000, dtype=np.float32)}
    for step in range(1, 4):
        state = {"w": np.array(state["w"])}
        state["w"][step] += 1.0
        ck.save(step, state)
    # keep=2 would drop step 1, but 2..3 are deltas chained to base 1
    assert ck.steps() == [1, 2, 3]
    _, loaded = ck.load(3)
    assert _bit_equal(loaded["w"], state["w"])


def test_file_ckpt_delta_degrades_to_full_on_big_change(tmp_path):
    ck = FileCheckpointer(str(tmp_path), delta_every=4)
    state = {"w": np.arange(30000, dtype=np.float32)}
    ck.save(1, state)
    state = {"w": state["w"] * 2.0}                    # 100% dirty
    ck.save(2, state)
    assert ck._manifest(2).kind == "full"


def test_file_ckpt_delta_corruption_detected(tmp_path):
    """A byte flipped in a *delta* frame fails the composed-state verify."""
    ck = FileCheckpointer(str(tmp_path), delta_every=4)
    state = {"w": np.arange(30000, dtype=np.float32)}
    ck.save(1, state)
    state = {"w": np.array(state["w"])}
    state["w"][7] += 1.0
    ck.save(2, state)
    assert ck._manifest(2).kind == "delta"
    shard = os.path.join(str(tmp_path), "step_0000000002", "shard_00000.bin")
    with open(shard, "r+b") as f:
        f.seek(os.path.getsize(shard) - 1)             # last data byte
        old = f.read(1)
        f.seek(os.path.getsize(shard) - 1)
        f.write(bytes([old[0] ^ 0x01]))
    with pytest.raises(IOError, match="corrupt"):
        ck.load(2)


def test_file_ckpt_async_delta_bit_exact(tmp_path):
    ck = FileCheckpointer(str(tmp_path), n_shards=2, delta_every=3)
    s1 = {"w": jnp.arange(20000.0)}
    ck.save(1, s1, async_=True)
    s2 = {"w": jnp.arange(20000.0).at[77].set(-5.0)}
    ck.save(2, s2, async_=True)
    ck.wait()
    assert ck._manifest(2).kind == "delta"
    _, loaded = ck.load(2)
    assert tree_digest(loaded) == tree_digest(jax.device_get(s2))


# ------------------------------------------------------- BuddyStore tiering

def test_buddy_store_spills_cold_steps(tmp_path):
    s = BuddyStore(0, 4, retain=3, spill_dir=str(tmp_path), hot_steps=1)
    for step in range(1, 8):
        s.save(step, bytes([step]) * 256)
    m = s.local_map()
    assert sorted(m) == [4, 5, 6, 7]
    assert all(m[k] == bytes([k]) * 256 for k in m)
    assert s.spilled_bytes == 3 * 256           # 4,5,6 cold
    assert s.resident_bytes() == 256            # only 7 hot
    assert len(os.listdir(str(tmp_path))) == 3


def test_buddy_store_spill_eviction_deletes_files(tmp_path):
    s = BuddyStore(0, 2, retain=1, spill_dir=str(tmp_path), hot_steps=1)
    s.hold(1, 1, b"a" * 64)
    s.hold(1, 2, b"b" * 64)
    s.hold(1, 9, b"c" * 64)                     # window slides past 1, 2
    assert sorted(s.held_map(1)) == [9]
    assert s.spilled_bytes == 0
    assert os.listdir(str(tmp_path)) == []


def test_buddy_store_spilled_delta_chain_stays_composable(tmp_path):
    """The spill tier keeps a delta's whole chain alive and composable
    even when the chain's base has slid out of the retention window."""
    base = {"x": np.arange(3000, dtype=np.float32)}
    s = BuddyStore(0, 4, retain=1, spill_dir=str(tmp_path), hot_steps=1)
    s.save(1, serde.to_bytes(base, {"step": 1}))
    tiles = serde.tile_digests(base)
    cur = base
    for step in range(2, 6):
        cur = {"x": np.array(cur["x"])}
        cur["x"][step] += 1.0
        plan = serde.delta_plan(cur, tiles)
        s.save(step, serde.to_delta_bytes(cur, plan, base_step=step - 1,
                                          extra={"step": step}))
        tiles = plan.new_tiles
    m = s.local_map()
    comp = serde.composable_steps(m)
    assert 5 in comp and 4 in comp
    extra, flat = serde.compose(m, 5)
    assert extra == {"step": 5}
    assert _bit_equal(flat["x"], cur["x"])
