"""Pallas kernel correctness in INTERPRET mode on the CPU mesh: this
checks the kernels' arithmetic and indexing only. That the installed
Mosaic compiler accepts them (interpret=False, at the store's row width)
is checked on the chip by `chip_smoke.py`, part "kernels"."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


def test_gather_rows_matches_xla():
    from adapm_tpu.ops.pallas_kernels import gather_rows
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(128, 256)).astype(np.float32))
    block_rows = 8
    idx = jnp.asarray(rng.integers(0, 128 // block_rows, 10)
                      .astype(np.int32))
    got = gather_rows(pool, idx, block_rows=block_rows, interpret=True)
    ref = np.asarray(pool).reshape(-1, block_rows, 256)[
        np.asarray(idx)].reshape(-1, 256)
    assert np.allclose(np.asarray(got), ref)


def test_adagrad_apply_matches_numpy():
    from adapm_tpu.ops.pallas_kernels import adagrad_apply
    rng = np.random.default_rng(1)
    n, L = 512, 128
    g = rng.normal(size=(n, L)).astype(np.float32)
    emb = rng.normal(size=(n, L)).astype(np.float32)
    acc = np.abs(rng.normal(size=(n, L))).astype(np.float32)
    lr, eps = 0.1, 1e-10
    new_emb, new_acc = adagrad_apply(jnp.asarray(g), jnp.asarray(emb),
                                     jnp.asarray(acc), lr, eps,
                                     interpret=True)
    ref_acc = acc + g * g
    ref_emb = emb - lr * g / np.sqrt(ref_acc + eps)
    assert np.allclose(np.asarray(new_acc), ref_acc, rtol=1e-5)
    assert np.allclose(np.asarray(new_emb), ref_emb, rtol=1e-4, atol=1e-6)


def _scatter_case(name):
    """(pool slots, chunk rows, slots of the batch[, positions a kernel
    call]) for one case of test_scatter_add_rows_matches_numpy."""
    rng = np.random.default_rng(25)
    N, R = 64, 8
    if name == "no_duplicates":
        return N, R, rng.permutation(N)[:24]
    if name == "uniform_duplicates":
        return N, R, rng.integers(0, N, 40)
    if name == "one_slot_half_the_batch":  # a run over many chunks
        s = rng.integers(0, N, 48)
        s[rng.permutation(48)[:24]] = 13
        return N, R, s
    if name == "run_ends_on_chunk_boundary":
        # sorted: [3]*8 | [5]*4, [9]*4 | ...: the run of 3 fills chunk 0
        return N, R, rng.permutation(
            np.array([3] * 8 + [5] * 4 + [9] * 4 + [17] * 8))
    if name == "n_not_a_multiple_of_chunk":
        return N, R, rng.integers(0, N, 21)
    if name == "out_of_range_dropped":
        s = rng.integers(0, N, 24)
        s[[1, 7, 20]] = [N, 2**31 - 2, -N - 1]
        s[4] = -1  # wraps to the last row, as jnp indexing does
        return N, R, s
    if name == "n_smaller_than_chunk":
        return N, 16, np.array([5, 5, 2])
    if name == "one_group_many_rows":  # 8 rows of one tile, interleaved
        return N, R, rng.integers(8, 16, 30)
    # more positions than one kernel call takes (here 16, two chunks; in
    # the step writeback.MAX_POSITIONS): successive calls on the pool
    if name == "many_calls_run_cut_by_each":  # one slot over 3 calls
        s = rng.integers(0, N, 70)  # and a last call of one chunk
        s[rng.permutation(70)[:40]] = 29
        return N, R, s, 16
    if name == "many_calls_group_cut_between_rows":
        # sorted: 14 positions of slot 8, then slots 9, 10 of the same
        # group: the call's end falls inside the group, not inside a row
        return N, R, rng.permutation(
            np.array([8] * 14 + [9] * 3 + [10] * 3 + [40] * 12)), 16
    if name == "many_calls_dropped_tail":  # a whole call of dropped slots
        s = rng.integers(0, N, 48)
        s[rng.permutation(48)[:20]] = N + 3
        return N, R, s, 16
    if name == "many_calls_chunk_not_a_divisor":  # calls of 24, not 30
        return N, 24, rng.integers(0, N, 100), 30
    # the codes of PR 48: the kernel visits the chunks that hold a valid
    # position and sums every position of those without asking
    if name == "all_invalid":  # no chunk visited: the pool as it was
        return N, R, np.array([N, N + 9, 2**31 - 2, -N - 1] * 5)
    if name == "many_calls_all_invalid":
        return N, R, np.full(40, N + 1), 16
    if name == "valid_end_mid_chunk":
        # sorted: 40, 40, 41, then 11 positions in the pool's LAST group,
        # then 9 dropped: chunk 1 is visited for 6 valid positions, and
        # its 2 invalid ones sum into buffer places that are never
        # written back
        return N, R, rng.permutation(np.array(
            [40, 40, 41, 56, 57, 58, 59, 63, 63, 60, 61, 62, 63, 56]
            + [N + 4] * 9))
    if name == "run_opens_mid_chunk_crosses_chunk_and_call":
        # sorted: [2]*3, then 29 times slot 29: its run opens at chunk
        # place 3, continues at place 0 of chunk 1, is cut by the call's
        # end after 16 positions and by the next after 32
        return N, R, rng.permutation(np.array([2] * 3 + [29] * 29 + [30])), \
            16
    if name == "row_named_more_often_than_a_chunk_holds":
        s = rng.integers(0, N, 60)
        s[rng.permutation(60)[:27]] = 41  # 27 > 3 chunks of 8
        return N, R, s
    raise KeyError(name)


SCATTER_CASES = [
    "no_duplicates", "uniform_duplicates", "one_slot_half_the_batch",
    "run_ends_on_chunk_boundary", "n_not_a_multiple_of_chunk",
    "out_of_range_dropped", "n_smaller_than_chunk", "one_group_many_rows",
    "many_calls_run_cut_by_each", "many_calls_group_cut_between_rows",
    "many_calls_dropped_tail", "many_calls_chunk_not_a_divisor",
    "all_invalid", "many_calls_all_invalid", "valid_end_mid_chunk",
    "run_opens_mid_chunk_crosses_chunk_and_call",
    "row_named_more_often_than_a_chunk_holds"]


@pytest.mark.parametrize("case", SCATTER_CASES)
def test_scatter_add_rows_matches_numpy(case):
    """The additions are the batch's, in the batch's order within a row
    (the sort is stable), so the result is `np.add.at`'s bit for bit."""
    from adapm_tpu.ops.pallas_kernels import scatter_add_rows
    N, R, slots, per_call = (*_scatter_case(case), None)[:4]
    slots = np.asarray(slots, dtype=np.int32)
    rng = np.random.default_rng(7)
    L = 128
    pool = rng.normal(size=(N, L)).astype(np.float32)
    upd = rng.normal(size=(len(slots), L)).astype(np.float32)
    got = np.asarray(scatter_add_rows(
        jnp.asarray(pool), jnp.asarray(slots), jnp.asarray(upd),
        chunk_rows=R, interpret=True, max_positions=per_call))
    wrapped = np.where(slots < 0, slots.astype(np.int64) + N, slots)
    keep = (wrapped >= 0) & (wrapped < N)
    ref = pool.copy()
    np.add.at(ref, wrapped[keep], upd[keep])
    assert got.tobytes() == ref.tobytes()
    untouched = np.setdiff1d(np.arange(N), wrapped[keep])
    assert got[untouched].tobytes() == pool[untouched].tobytes()


def _adagrad_rows(g, acc, lr, eps):
    """numpy float32 AdaGrad update rows [d emb | d acc], from the
    equations (apps/mf/update.h): nothing of the program's."""
    g2 = g * g
    return np.concatenate(
        [-np.float32(lr) * g / np.sqrt(acc + g2 + np.float32(eps)), g2],
        axis=1).astype(np.float32)


def _assert_adagrad_rows_landed(got, pool, landed, upd):
    """`got` is `pool` with `upd` rows added at `landed` slots, batch
    order: the accumulator half (g*g: exact in any implementation) bit
    for bit, the embedding half within 2 ulp of the magnitudes summed
    (rsqrt against numpy's 1/sqrt: the two differ by up to 2 ulp an
    update row), untouched rows bit for bit."""
    H = pool.shape[1] // 2
    ref = pool.copy()
    np.add.at(ref, landed, upd)
    assert got[:, H:].tobytes() == ref[:, H:].tobytes()
    mag = np.abs(pool)
    np.add.at(mag, landed, np.abs(upd))
    assert (np.abs(got - ref) <= 2 * np.spacing(mag)).all()
    untouched = np.setdiff1d(np.arange(len(pool)), landed)
    assert got[untouched].tobytes() == pool[untouched].tobytes()


@pytest.mark.parametrize("case", SCATTER_CASES)
def test_scatter_adagrad_rows_matches_numpy(case):
    """The kernel's AdaGrad form on the plain form's cases (duplicates
    inside a chunk, runs cut by a chunk's and by each call's end,
    dropped and padding positions, a whole call of dropped slots): the
    update rows formed in the kernel from gradients and gathered
    accumulators are numpy's, added in the batch's order."""
    from adapm_tpu.ops.pallas_kernels import scatter_adagrad_rows
    N, R, slots, per_call = (*_scatter_case(case), None)[:4]
    slots = np.asarray(slots, dtype=np.int32)
    rng = np.random.default_rng(29)
    L, lr, eps = 256, 0.1, 1e-10
    pool = rng.normal(size=(N, L)).astype(np.float32)
    pool[:, L // 2:] = np.abs(pool[:, L // 2:])
    g = rng.normal(size=(len(slots), L // 2)).astype(np.float32)
    acc = np.abs(rng.normal(size=(len(slots), L // 2))).astype(np.float32)
    got = np.asarray(scatter_adagrad_rows(
        jnp.asarray(pool), jnp.asarray(slots), jnp.asarray(g),
        jnp.asarray(acc), lr, eps, chunk_rows=R, interpret=True,
        max_positions=per_call))
    wrapped = np.where(slots < 0, slots.astype(np.int64) + N, slots)
    keep = (wrapped >= 0) & (wrapped < N)
    _assert_adagrad_rows_landed(got, pool, wrapped[keep],
                                _adagrad_rows(g, acc, lr, eps)[keep])


def test_scatter_adagrad_accumulator_is_the_operand():
    """One key in two roles of one pool, written back one role after
    the other, each with the accumulator ITS gradient was gathered with
    (and they differ): every update is formed from the operand, never
    from the pool's row, which by the second role holds the first's
    g*g; inside one call (the key twice in a role) the same."""
    from adapm_tpu.ops.pallas_kernels import scatter_adagrad_rows
    rng = np.random.default_rng(30)
    N, L, R, lr, eps = 64, 256, 8, 0.1, 1e-10
    pool = rng.normal(size=(N, L)).astype(np.float32)
    pool[:, L // 2:] = np.abs(pool[:, L // 2:])
    roles = []
    for slots in ([13, 5, 13, 40], [7, 13, 22]):  # the key of slot 13
        slots = np.array(slots, dtype=np.int32)
        g = rng.normal(size=(len(slots), L // 2)).astype(np.float32)
        acc = (rng.uniform(0.5, 4.0, size=(len(slots), 1)) * np.abs(
            rng.normal(size=(len(slots), L // 2)))).astype(np.float32)
        roles.append((slots, g, acc))
    got = jnp.asarray(pool)
    for slots, g, acc in roles:
        got = scatter_adagrad_rows(got, jnp.asarray(slots), jnp.asarray(g),
                                   jnp.asarray(acc), lr, eps, chunk_rows=R,
                                   interpret=True)
    got = np.asarray(got)
    landed = np.concatenate([r[0] for r in roles])
    _assert_adagrad_rows_landed(got, pool, landed, np.concatenate(
        [_adagrad_rows(g, acc, lr, eps) for _, g, acc in roles]))
    # and not what the pool's accumulator would have given: role two's
    # update of slot 13 from the pool's row as role one left it
    H = L // 2
    (slots, g, acc), (_, g2, _) = roles
    from_pool = pool[13].copy()
    for j in (0, 2):
        from_pool += _adagrad_rows(g[j:j + 1], acc[j:j + 1], lr, eps)[0]
    from_pool += _adagrad_rows(g2[1:2], from_pool[None, H:], lr, eps)[0]
    assert not np.allclose(got[13, :H], from_pool[:H], rtol=1e-3, atol=0)


def test_sorted_slices_are_whole_calls():
    """Each slice of the sorted positions is a call the kernel can make
    alone: whole chunks, its first run opened and its last closed inside
    it, and together they hold every position that lands, once."""
    from adapm_tpu.ops.writeback import (CLOSES, OPENS, SLOT_MASK,
                                         sorted_slices)
    rng = np.random.default_rng(11)
    slots = rng.integers(0, 64, 200).astype(np.int32)
    slots[:90] = 17            # one run over several slices
    slots[190:] = 64           # dropped
    slices = sorted_slices(jnp.asarray(slots), 64, 8, max_positions=44)
    assert [int(c.shape[0]) for c, _ in slices] == [40] * 5
    seen = []
    for codes, perm in slices:
        codes, perm = np.asarray(codes), np.asarray(perm)
        live = codes >= 0
        if live.any():
            assert codes[live][0] & OPENS and codes[live][-1] & CLOSES
        assert (slots[perm[live]] == codes[live] & SLOT_MASK).all()
        seen.extend(perm[live])
    assert sorted(seen) == list(range(190))
    # the batch's order within a slot survives the cut (stable sort)
    assert [p for p in seen if p < 90] == list(range(90))


@pytest.mark.parametrize("case, visited", [
    ("all_invalid", [0]),
    ("many_calls_all_invalid", [0, 0, 0]),
    ("valid_end_mid_chunk", [2]),          # 14 valid of 24 positions
    ("many_calls_dropped_tail", [2, 2, 0]),  # 28 valid, 20 dropped
    ("no_duplicates", [3]),                # every chunk
    ("n_not_a_multiple_of_chunk", [3]),    # 21 valid, padded to 24
])
def test_invalid_tail_is_not_visited(case, visited):
    """The valid positions of a call are a prefix of it (invalid slots
    sort last), and the kernel is told how many chunks hold one: the
    last word of `chunk_meta`. The words before it count each chunk's
    copies: the runs it opens and closes."""
    from adapm_tpu.ops.writeback import (CLOSES, OPENS, chunk_meta,
                                         sorted_slices)
    N, R, slots, per_call = (*_scatter_case(case), None)[:4]
    slices = sorted_slices(jnp.asarray(slots, dtype=jnp.int32), N, R,
                           per_call)
    assert len(slices) == len(visited)
    for (codes, _), chunks in zip(slices, visited):
        meta, codes = np.asarray(chunk_meta(codes, R)), np.asarray(codes)
        assert meta[-1] == chunks == -(-int((codes >= 0).sum()) // R)
        assert (codes[:(codes >= 0).sum()] >= 0).all()
        by_chunk = codes.reshape(-1, R)
        assert (meta[:-1] & 255 == ((by_chunk & OPENS) != 0).sum(1)).all()
        assert (meta[:-1] >> 8 == ((by_chunk & CLOSES) != 0).sum(1)).all()
        # an invalid position opens and closes nothing
        assert not (by_chunk[by_chunk < 0] & (OPENS | CLOSES)).any()


@pytest.mark.parametrize("case", [
    "one_slot_half_the_batch", "run_opens_mid_chunk_crosses_chunk_and_call",
    "valid_end_mid_chunk", "many_calls_chunk_not_a_divisor"])
def test_codes_carry_the_place_of_the_runs_group(case):
    """Bits 24-28 of a code are the kernel's `tgt`, decided in
    `sort_slots`: the chunk position of the last position at or before
    it that opens a run, 0 where none does (the run continues from the
    chunk before, whose group the kernel moves to place 0); an invalid
    position names its own place, where no run's group lives."""
    from adapm_tpu.ops.writeback import (OPENS, SLOT_MASK, TGT_MASK,
                                         TGT_SHIFT, sorted_slices)
    N, R, slots, per_call = (*_scatter_case(case), None)[:4]
    slots = np.asarray(slots, dtype=np.int32)
    seen_mid_chunk_run = False
    for codes, perm in sorted_slices(jnp.asarray(slots), N, R, per_call):
        codes, perm = np.asarray(codes), np.asarray(perm)
        tgt = (codes >> TGT_SHIFT) & TGT_MASK
        for c in range(0, len(codes), R):
            place = 0
            for j in range(R):
                code = codes[c + j]
                if code < 0:
                    assert tgt[c + j] == j and code & SLOT_MASK == 0
                    continue
                if code & OPENS:
                    place = j
                assert tgt[c + j] == place
                assert code & SLOT_MASK == slots[perm[c + j]]
                seen_mid_chunk_run |= place > 0 and not code & OPENS
    assert seen_mid_chunk_run


@pytest.mark.parametrize("n_slots, fits", [
    (1 << 24, True), ((1 << 24) + 8, False), (1 << 29, False)])
def test_slots_beyond_a_codes_bits_are_refused(n_slots, fits):
    """A code has 24 bits of slot: the static rule keeps a larger pool
    on XLA's scatter, and `sort_slots` refuses it outright."""
    from adapm_tpu.ops import fused, writeback
    pool = jax.ShapeDtypeStruct((1, n_slots, 256), jnp.float32)
    assert fused.writeback_uses_kernel(pool, backend="tpu") is fits
    slots = jnp.zeros((8,), jnp.int32)
    if fits:
        writeback.sort_slots(slots, n_slots, 8)
    else:
        with pytest.raises(AssertionError):
            writeback.sort_slots(slots, n_slots, 8)


def test_exported_kernel_is_kept_read_back_and_remade(kernel_cache,
                                                      monkeypatch):
    """The way the step takes the kernel on any backend (here its
    interpret build): exported once into the compile cache directory,
    deserialised by the next process, the same rows either way; made
    anew, not raised over, when the file is cut short; and under
    another name as soon as either source file changes."""
    from adapm_tpu.ops import writeback
    N, L, R = 64, 128, 8
    rng = np.random.default_rng(12)
    slots = rng.integers(0, N, 24).astype(np.int32)
    pool = rng.normal(size=(N, L)).astype(np.float32)
    upd = rng.normal(size=(24, L)).astype(np.float32)
    ref = pool.copy()
    np.add.at(ref, slots, upd)
    ((codes, perm),) = writeback.sorted_slices(jnp.asarray(slots), N, R)

    def run(exported):
        return np.asarray(jax.jit(exported.call)(
            jnp.asarray(pool), codes, jnp.asarray(upd)[perm]))

    made = writeback.exported_kernel(N, L, 24, R, "cpu")
    assert run(made).tobytes() == ref.tobytes()
    (kept,) = kernel_cache.iterdir()
    whole = kept.read_bytes()
    writeback.exported_kernel.cache_clear()  # as a later process
    read = writeback.exported_kernel(N, L, 24, R, "cpu")
    assert read is not made
    assert read.mlir_module_serialized == made.mlir_module_serialized
    assert run(read).tobytes() == ref.tobytes()

    kept.write_bytes(whole[:len(whole) // 2])
    writeback.exported_kernel.cache_clear()
    remade = writeback.exported_kernel(N, L, 24, R, "cpu")
    assert run(remade).tobytes() == ref.tobytes()
    assert kept.read_bytes() == whole

    monkeypatch.setattr(writeback, "_sources_sha", lambda: b"edited")
    writeback.exported_kernel.cache_clear()
    writeback.exported_kernel(N, L, 24, R, "cpu")
    assert len(list(kernel_cache.iterdir())) == 2


@pytest.mark.parametrize("n, max_positions, calls", [
    (1000, 256, 4),      # four calls, as the DLRM cell's 438,272 positions
    (1000, 1 << 17, 1),  # the same positions in one
])
def test_role_larger_than_one_call_at_narrow_rows(n, max_positions, calls,
                                                  kernel_cache):
    """A role of more positions than one kernel call takes, at rows of
    256 floats (1 KB, chunks of 32 positions): the sorted positions cut
    into `calls` slices, each call on the pool the call before returned,
    against `.at[].add`. Runs of one slot (a one-row table named by every
    example) and of one 8-row group cross the slices' ends."""
    from adapm_tpu.ops import writeback
    N, L = 96, 256
    R = writeback.chunk_rows_for(L)
    assert R == 32
    rng = np.random.default_rng(21)
    slots = rng.integers(0, N, n).astype(np.int32)
    slots[100:500] = 40               # one slot 400 times: over two cuts
    slots[500:520] = N + 3            # out of the pool: dropped
    pool = rng.normal(size=(N, L)).astype(np.float32)
    upd = rng.normal(size=(n, L)).astype(np.float32)
    ref = np.asarray(jnp.asarray(pool).at[jnp.asarray(slots)].add(
        jnp.asarray(upd), mode="drop"))
    slices = writeback.sorted_slices(jnp.asarray(slots), N, R,
                                     max_positions=max_positions)
    assert len(slices) == calls
    got = jnp.asarray(pool)
    for codes, perm in slices:
        got = writeback.exported_kernel(N, L, int(codes.shape[0]), R,
                                        "cpu").call(
            got, codes, jnp.asarray(upd)[perm])
    got = np.asarray(got)
    # up to 400 float32 additions into one row, in the batch's order by
    # the kernel and in XLA's by the scatter: a few ulp of the sum
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    untouched = np.setdiff1d(np.arange(N), slots)
    assert got[untouched].tobytes() == pool[untouched].tobytes()
