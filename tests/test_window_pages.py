"""The second page pool of a model that mixes window and full attention
by layer (``DSStateManager(window=...)``), on the host alone: a window
page goes back exactly when its last row has left the window, never
earlier under a chunk that straddles it; a sequence holds a bounded
number of them whatever its context; both pools exhaust and recover; flush
returns everything; the two further arrays ride the one index buffer."""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  window_step_counts)
from deepspeed_tpu.inference.v2.ragged import (DSStateManager,
                                               KVCacheExhausted, PackedIndex,
                                               build_ragged_batch)
from deepspeed_tpu.models import get_model_config

BS, WINDOW, BUDGET = 8, 24, 16


def manager(full=64, window_blocks=16, max_seqs=4):
    return DSStateManager(max_seqs=max_seqs, num_blocks=full, block_size=BS,
                          max_blocks_per_seq=32, window=WINDOW,
                          window_blocks=window_blocks)


def run(mgr, seq, n):
    """One step of ``n`` rows of ``seq``; the batch."""
    return build_ragged_batch([(seq, n)], mgr, BUDGET)


def live(seq):
    return [b for b in seq.window_blocks if b]


@pytest.mark.parametrize("chunk", [1, 3, 8, 11, 16])
def test_a_page_goes_back_when_its_last_row_left_the_window(chunk):
    """Whatever the chunking, at the start of every step: page j is held
    iff some row from the sequence's next position on can still see one of
    its rows, i.e. (j + 1) * BS > next - WINDOW."""
    mgr = manager()
    seq = mgr.open(1, list(range(200)))
    while seq.uncached:
        nxt = seq.num_cached
        run(mgr, seq, min(chunk, seq.uncached))
        for j, page in enumerate(seq.window_blocks):
            gone = (j + 1) * BS <= nxt - WINDOW
            assert (page == 0) == gone, (nxt, j)
        # never more than ceil((window + budget) / page) + 1
        assert len(live(seq)) <= -(-(WINDOW + BUDGET) // BS) + 1
        # the full pool keeps everything
        assert len(seq.blocks) == -(-seq.num_cached // BS)
    assert mgr.allocator.free_blocks == 63 - 25
    mgr.flush(1)
    assert mgr.allocator.free_blocks == 63
    assert mgr.window_allocator.free_blocks == 15


def test_a_straddling_chunk_keeps_the_page_its_first_row_sees():
    """Next position 40, a chunk of 16: its first row (40) sees 17..40, so
    page 2 (rows 16..23) stays although the chunk's last row (55) sees
    nothing of it; the step after (next 56) returns pages 2 and 3."""
    mgr = manager()
    seq = mgr.open(1, list(range(100)))
    for n in (16, 16, 8):
        run(mgr, seq, n)
    assert seq.num_cached == 40 and seq.window_freed == 1
    run(mgr, seq, 16)
    assert seq.window_freed == 2 and seq.window_blocks[2] != 0
    assert mgr.pages_freed == 1
    run(mgr, seq, 16)
    assert seq.window_freed == 4 and mgr.pages_freed == 2


def test_the_index_carries_both_tables_and_both_destinations():
    mgr = manager()
    seq = mgr.open(1, list(range(60)))
    for _ in range(3):
        rb = run(mgr, seq, 16)
    index = rb.index
    assert index.window and index.buf.size == PackedIndex.size(
        16, 5, index.blocks, False, True)
    dest, tables = index.window_arrays()
    _, _, pos, full_dest, full_tables, *_ = index.arrays()
    assert tables.shape == full_tables.shape
    np.testing.assert_array_equal(pos, np.arange(32, 48))
    row = tables[seq.slot]
    assert row[0] == 0 and list(row[1:6]) == seq.window_blocks[1:6]
    np.testing.assert_array_equal(dest, row[pos // BS] * BS + pos % BS)
    # the two pools hand out their own pages: the destinations differ
    assert list(full_tables[seq.slot][:6]) == seq.blocks
    assert not np.array_equal(dest, full_dest) or seq.blocks[4:6] \
        == seq.window_blocks[4:6]
    # a model without the second pool builds the buffer it built before
    plain = DSStateManager(4, 64, BS, 32)
    rb = build_ragged_batch([(plain.open(1, list(range(20))), 16)], plain,
                            BUDGET)
    assert rb.index.window_arrays() is None
    assert rb.index.buf.size == PackedIndex.size(16, 5, rb.index.blocks)


@pytest.mark.parametrize("short", ["window", "full"])
def test_either_pool_exhausts_and_recovers(short):
    """The pool that runs out raises ``KVCacheExhausted`` with the
    sequence untouched; a flush of another sequence lets the step run."""
    mgr = (manager(full=64, window_blocks=6) if short == "window"
           else manager(full=6, window_blocks=16))
    a = mgr.open(1, list(range(100)))
    b = mgr.open(2, list(range(100)))
    run(mgr, a, 16)
    run(mgr, a, 16)                             # four pages of five
    run(mgr, b, 8)                              # the fifth
    with pytest.raises(KVCacheExhausted):
        run(mgr, b, 16)
    assert b.num_cached == 8
    mgr.flush(1)
    run(mgr, b, 16)
    assert b.num_cached == 24
    mgr.flush(2)
    assert mgr.allocator.free_blocks == mgr.allocator.num_blocks - 1
    assert mgr.window_allocator.free_blocks \
        == mgr.window_allocator.num_blocks - 1


def test_a_long_sequence_fits_a_window_pool_smaller_than_its_context():
    """200 positions are 25 pages; the window pool has 7."""
    mgr = manager(window_blocks=8)
    seq = mgr.open(1, list(range(200)))
    while seq.uncached:
        run(mgr, seq, min(BUDGET, seq.uncached))
    assert len(seq.blocks) == 25 and len(live(seq)) <= 6
    assert mgr.pages_held() == (25, len(live(seq)))


def test_the_schedule_spans_counts():
    model = get_model_config("trinity-tiny")
    counts = window_step_counts([(40, 16), (0, 5), (90, 1)], model, (12, 7),
                                3)
    assert counts == {"full_kv_rows": 56 + 5 + 91,
                      "window_kv_rows": 24 + 5 + 24,
                      "full_pages": 12, "window_pages": 7, "pages_freed": 3,
                      # live pairs: causal, and cut to the window of 24
                      "full_qk_pairs": sum(range(41, 57)) + 15 + 91,
                      "window_qk_pairs": 16 * 24 + 15 + 24,
                      "expert_rows": 22 * 4 * 4 / 16}


def test_admission_counts_both_pools():
    """``free_blocks`` and ``cfg.num_blocks`` stay the full pool's; the
    window pool is counted by what a sequence holds of it at its widest."""
    eng = InferenceEngineV2(get_model_config("trinity-tiny"), {
        "dtype": "float32",
        "memory_config": {"num_blocks": 48, "window_blocks": 8,
                          "block_size": BS},
        "max_context": 160,
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_batch_size": BUDGET}})
    assert eng.free_blocks == 47 and eng.free_window_blocks == 7
    assert eng.window_seq_blocks(20) == 3
    assert eng.window_seq_blocks(150) == 6      # ceil((24 + 16) / 8) + 1
    assert eng.window_admissible(150)
    eng.admit(1, list(range(20)))
    eng.step()
    assert eng.free_window_blocks == 5 and not eng.window_admissible(150)
    assert eng.window_admissible(30)
    eng.flush(1)
    assert eng.free_window_blocks == 7
    # 108 positions are 14 pages; the pool's 7 serve them in turn
    assert len(eng.generate([list(range(100))], max_new_tokens=8)[0]) == 8
    assert eng.free_window_blocks == 7 and eng.free_blocks == 47
    plain = InferenceEngineV2(get_model_config("mistral-tiny"), {
        "dtype": "float32", "memory_config": {"num_blocks": 16,
                                              "block_size": BS},
        "max_context": 64})
    assert plain.free_window_blocks == 0 and plain.window_seq_blocks(50) == 0
    assert plain.window_admissible(10 ** 6)
