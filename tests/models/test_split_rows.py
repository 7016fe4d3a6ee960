"""split_packed_rows: the batched split against a per-row oracle.

The oracle below is the per-row loop the batched split replaced, kept
here as written. Both lanes must give the oracle's rows byte for byte
(dtype, shape, C-contiguity included), each row owning its arrays, and
split → splice must keep every verdict of the unsplit batch.
"""

import numpy as np
import pytest

from kyverno_tpu.models import flatten
from kyverno_tpu.models.compiler import STR_LEN
from kyverno_tpu.models.flatten import (
    SPLIT_BATCHED_MIN_ROWS,
    PackedBatch,
    PackedRow,
    split_packed_rows,
    splice_packed_rows,
)
from kyverno_tpu.runtime import tracing


def oracle_split(batch: PackedBatch) -> list[PackedRow]:
    cells, bmeta = np.asarray(batch.cells), np.asarray(batch.bmeta)
    str_bytes, dictv = np.asarray(batch.str_bytes), np.asarray(batch.dictv)
    rows: list[PackedRow] = []
    for b in range(int(batch.n)):
        rc = cells[b]                             # [P, E, 2]
        used = rc.any(axis=2).any(axis=0)         # [E] slot columns in use
        e_row = int(np.max(np.nonzero(used)[0]) + 1) if used.any() else 0
        rc = rc[:, :e_row, :]
        w0 = rc[..., 0]
        ids = np.unique(w0)
        ids = (ids[ids > 0] - 1).astype(np.int64)
        lut = np.zeros(int(dictv.shape[0]) + 1, dtype=np.uint32)
        lut[ids + 1] = np.arange(1, len(ids) + 1, dtype=np.uint32)
        rc = np.stack([lut[w0], rc[..., 1]], axis=-1)
        rows.append(PackedRow(
            cells=np.ascontiguousarray(rc),
            bmeta=int(bmeta[b]),
            str_bytes=np.ascontiguousarray(str_bytes[ids]),
            dictv=np.ascontiguousarray(dictv[ids]),
        ))
    return rows


def random_batch(rng, n, P=5, E=4, V=40, dead=0.5, strings=0.6):
    """A packed batch with random trailing dead slot columns per row,
    word0 ids drawn from a shared table (so rows interleave ids and
    repeat them) and word1 meta on most cells."""
    cells = np.zeros((n, P, E, 2), dtype=np.uint32)
    for b in range(n):
        e_row = int(rng.integers(0, E + 1))
        live = rng.random((P, e_row)) > dead
        w0 = rng.integers(1, V + 1, size=(P, e_row), dtype=np.uint32)
        w0[rng.random((P, e_row)) > strings] = 0
        cells[b, :, :e_row, 0] = np.where(live, w0, 0)
        cells[b, :, :e_row, 1] = np.where(
            live, rng.integers(1, 2 ** 32, size=(P, e_row), dtype=np.uint32),
            0)
        if e_row and P:   # the row's last column is in use on a path
            cells[b, int(rng.integers(0, P)), e_row - 1, 1] |= 1 << 19
    return PackedBatch(
        n=n, e=E, cells=cells,
        bmeta=rng.integers(0, 2 ** 32, size=n, dtype=np.uint32),
        str_bytes=rng.integers(0, 256, size=(V, STR_LEN), dtype=np.uint8),
        dictv=rng.integers(0, 2 ** 32, size=(V, 5), dtype=np.uint32))


def assert_same_rows(got: list[PackedRow], want: list[PackedRow],
                     batch: PackedBatch) -> None:
    assert len(got) == len(want)
    owned = []
    for g, w in zip(got, want):
        assert type(g.bmeta) is int and g.bmeta == w.bmeta
        for name in ("cells", "str_bytes", "dictv"):
            a, b = getattr(g, name), getattr(w, name)
            assert a.dtype == b.dtype, name
            assert a.shape == b.shape, name
            assert a.flags["C_CONTIGUOUS"], name
            assert a.tobytes() == b.tobytes(), name
            for src in batch.packed_args():
                assert not np.shares_memory(a, src), name
            owned.append(a)
        assert g.nbytes == w.nbytes
    # no two rows share a buffer either
    for i, a in enumerate(owned):
        for b in owned[i + 1:]:
            assert not np.shares_memory(a, b)


def _split_lane(batch: PackedBatch) -> tuple[list[PackedRow], dict]:
    rec = tracing.recorder()
    trace = rec.start("test_split")
    with tracing.active(trace):
        rows = split_packed_rows(batch)
    [span] = [s for s in trace.spans if s.name == "row_split"]
    return rows, span.labels


def _hand_batch(rows_w0: list, rows_w1: list, V: int = 12) -> PackedBatch:
    """A batch from explicit per-row [P, E] word0 / word1 grids."""
    cells = np.stack([np.stack([np.asarray(a, dtype=np.uint32),
                                np.asarray(b, dtype=np.uint32)], axis=-1)
                      for a, b in zip(rows_w0, rows_w1)])
    n = cells.shape[0]
    return PackedBatch(
        n=n, e=cells.shape[2], cells=cells,
        bmeta=np.arange(1, n + 1, dtype=np.uint32) * 0x10001,
        str_bytes=np.arange(V * STR_LEN, dtype=np.uint8).reshape(V, STR_LEN),
        dictv=np.arange(V * 5, dtype=np.uint32).reshape(V, 5) + 7)


HAND = {
    # slots live by meta alone in row 0; row 1 holds no used slot at all
    "empty_row": ([[[0, 0, 0], [0, 0, 0]], [[0, 0, 0], [0, 0, 0]],
                   [[3, 0, 0], [0, 0, 0]]],
                  [[[5, 9, 0], [0, 0, 0]], [[0, 0, 0], [0, 0, 0]],
                   [[1, 0, 0], [0, 0, 0]]]),
    # no strings anywhere: word0 all zero, cells live by meta
    "no_strings": ([[[0, 0, 0], [0, 0, 0]]] * 3,
                   [[[1, 2, 0], [3, 0, 0]], [[0, 0, 4], [0, 0, 0]],
                    [[7, 0, 0], [0, 8, 0]]]),
    # one id many times in a row, beside others
    "repeated_ids": ([[[4, 4, 4], [4, 2, 4]], [[9, 9, 0], [9, 9, 0]],
                      [[1, 1, 1], [1, 1, 1]]],
                     [[[1, 1, 1], [1, 1, 1]], [[1, 1, 0], [1, 1, 0]],
                      [[1, 1, 1], [1, 1, 1]]]),
    # rows take alternate ids of one table, in descending cell order
    "interleaved_ids": ([[[11, 7, 3], [1, 0, 0]], [[12, 8, 4], [2, 0, 0]],
                         [[10, 6, 5], [9, 0, 0]], [[3, 12, 1], [0, 0, 0]]],
                        [[[1, 1, 1], [1, 0, 0]], [[1, 1, 1], [1, 0, 0]],
                         [[1, 1, 1], [1, 0, 0]], [[1, 1, 1], [0, 0, 0]]]),
}


class TestBatchedSplitMatchesOracle:
    @pytest.mark.parametrize("n", [
        0, 1, SPLIT_BATCHED_MIN_ROWS - 1, SPLIT_BATCHED_MIN_ROWS,
        SPLIT_BATCHED_MIN_ROWS + 1, 64])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_batches(self, n, seed):
        batch = random_batch(np.random.default_rng(seed * 1000 + n), n)
        assert_same_rows(split_packed_rows(batch), oracle_split(batch), batch)

    @pytest.mark.parametrize("case", sorted(HAND))
    def test_hand_cases(self, case):
        batch = _hand_batch(*HAND[case])
        assert batch.n >= SPLIT_BATCHED_MIN_ROWS     # the batched lane
        rows = split_packed_rows(batch)
        assert_same_rows(rows, oracle_split(batch), batch)
        # and each row alone, through the per-row lane
        for b in range(batch.n):
            one = PackedBatch(n=1, e=batch.e, cells=batch.cells[b:b + 1],
                              bmeta=batch.bmeta[b:b + 1],
                              str_bytes=batch.str_bytes, dictv=batch.dictv)
            assert_same_rows(split_packed_rows(one), rows[b:b + 1], one)

    def test_empty_row_trims_to_no_slots(self):
        rows = split_packed_rows(_hand_batch(*HAND["empty_row"]))
        assert rows[1].cells.shape == (2, 0, 2)
        assert rows[1].str_bytes.shape == (0, STR_LEN)
        assert rows[1].dictv.shape == (0, 5)
        assert rows[0].cells.shape == (2, 2, 2)

    @pytest.mark.parametrize("rows_per_block", [1, 3, 7])
    def test_more_than_one_block(self, monkeypatch, rows_per_block):
        batch = random_batch(np.random.default_rng(rows_per_block), 23)
        P, E = batch.cells.shape[1:3]
        monkeypatch.setattr(flatten, "SPLIT_BLOCK_CELLS",
                            rows_per_block * P * E)
        assert_same_rows(split_packed_rows(batch), oracle_split(batch), batch)

    @pytest.mark.parametrize("n", [1, SPLIT_BATCHED_MIN_ROWS + 2])
    def test_no_paths(self, n):
        batch = random_batch(np.random.default_rng(n), n, P=0)
        assert_same_rows(split_packed_rows(batch), oracle_split(batch), batch)

    def test_rows_past_n_are_left_out(self):
        """A batch padded past its ``n`` splits only its first n rows."""
        batch = random_batch(np.random.default_rng(5), 9)
        short = PackedBatch(n=6, e=batch.e, cells=batch.cells,
                            bmeta=batch.bmeta, str_bytes=batch.str_bytes,
                            dictv=batch.dictv)
        assert_same_rows(split_packed_rows(short), oracle_split(short), short)


class TestRowSplitLane:
    def test_one_row_takes_the_per_row_lane(self):
        batch = random_batch(np.random.default_rng(0), 1)
        rows, labels = _split_lane(batch)
        assert labels == {"rows": 1, "lane": "per_row"}
        assert_same_rows(rows, oracle_split(batch), batch)

    def test_batch_above_the_crossover_takes_the_batched_lane(self):
        n = SPLIT_BATCHED_MIN_ROWS + 5
        batch = random_batch(np.random.default_rng(1), n)
        rows, labels = _split_lane(batch)
        assert labels == {"rows": n, "lane": "batched"}
        assert_same_rows(rows, oracle_split(batch), batch)


def test_cell_policy_set_split_splice_keeps_verdicts():
    """The benchmark's best-practices set (autogen on) over a small
    ClusterLoader2-like namespace: the unsplit batch and its rows split
    and spliced back give the same verdicts on the device lane."""
    from benchmark import clusters, spec

    from kyverno_tpu.api.load import load_policy
    from kyverno_tpu.models import CompiledPolicySet
    from kyverno_tpu.policy.autogen import mutate_policy_for_autogen

    cell = spec.Cell(spec.load_benchmark(), "bp-scan-fresh")
    params = dict(cell.config["cluster"], namespaces=1)
    params["controllers"] = [dict(g, count=1, replicas=min(g["replicas"], 3))
                             for g in params["controllers"]]
    gen = clusters.generator(params["generator"])
    bodies = [gen.resource(i, "split", params) for i in range(gen.size(params))]
    cps = CompiledPolicySet([mutate_policy_for_autogen(load_policy(d))
                             for d in cell.policies()])
    batch = cps.flatten_packed(bodies)
    rows = split_packed_rows(batch)
    assert len(rows) == len(bodies) >= SPLIT_BATCHED_MIN_ROWS
    assert_same_rows(rows, oracle_split(batch), batch)
    direct = np.asarray(cps.evaluate_device(batch))
    spliced = np.asarray(cps.evaluate_device(splice_packed_rows(rows)))
    assert np.array_equal(direct, spliced)
