"""Data pipeline tests: tokenizer, tfrecord round-trip, collate, skip-resume,
multi-host sharding arithmetic."""

import numpy as np
import pytest

from progen_tpu.data import (
    collate,
    count_sequences,
    decode_tokens,
    encode_tokens,
    iterator_from_tfrecords_folder,
    parse_shard_filename,
    shard_filename,
    write_tfrecord,
)


def test_tokenizer_roundtrip():
    s = "MSKGEELFTG# [tax=Homo]"
    toks = encode_tokens(s)
    assert min(toks) >= 1  # id 0 reserved
    assert decode_tokens(np.asarray(toks)) == s


def test_decode_drops_pad():
    assert decode_tokens(np.asarray([0, 66, 0, 67, 0])) == "AB"


def test_shard_filename_protocol():
    name = shard_filename(3, 127, "train")
    assert name == "3.127.train.tfrecord.gz"
    assert parse_shard_filename(name) == 127
    assert parse_shard_filename("/some/dir/0.50.valid.tfrecord.gz") == 50


def test_collate_contract():
    seqs = [b"ABC", b"ABCDEFGHIJ"]
    out = collate(seqs, seq_len=5)
    assert out.shape == (2, 6) and out.dtype == np.int32
    # BOS column, +1 offset, right-pad
    np.testing.assert_array_equal(out[0], [0, 66, 67, 68, 0, 0])
    # truncation to seq_len
    np.testing.assert_array_equal(out[1], [0, 66, 67, 68, 69, 70])


@pytest.fixture()
def tfrecord_dir(tmp_path):
    seqs = [f"SEQ{i:03d}PROTEIN".encode() for i in range(20)]
    n1 = write_tfrecord(tmp_path / shard_filename(0, 12, "train"), seqs[:12])
    n2 = write_tfrecord(tmp_path / shard_filename(1, 8, "train"), seqs[12:])
    write_tfrecord(tmp_path / shard_filename(0, 4, "valid"),
                   [b"VALSEQ%d" % i for i in range(4)])
    assert (n1, n2) == (12, 8)
    return tmp_path


def test_roundtrip_and_counts(tfrecord_dir):
    num, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    assert num == 20
    assert count_sequences(str(tfrecord_dir), "valid") == 4
    batches = list(it_fn(seq_len=16, batch_size=8))
    assert [b.shape for b in batches] == [(8, 17), (8, 17), (4, 17)]
    got = decode_tokens(batches[0][0])
    assert got == "SEQ000PROTEIN"


def test_skip_resume_is_record_exact(tfrecord_dir):
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    full = np.concatenate(list(it_fn(seq_len=16, batch_size=4)))
    resumed = np.concatenate(list(it_fn(seq_len=16, batch_size=4, skip=6)))
    np.testing.assert_array_equal(resumed, full[6:])
    # resume correctness across batch-size change (README.md:112 claim)
    resumed2 = np.concatenate(list(it_fn(seq_len=16, batch_size=7, skip=6)))
    np.testing.assert_array_equal(resumed2, full[6:])


def test_multihost_sharding_partitions_records(tfrecord_dir):
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    full = np.concatenate(list(it_fn(seq_len=16, batch_size=4)))
    shards = [
        np.concatenate(list(it_fn(seq_len=16, batch_size=2,
                                  process_count=2, process_index=i)))
        for i in range(2)
    ]
    assert sum(s.shape[0] for s in shards) == full.shape[0]
    # disjoint and complete: every record appears exactly once across hosts
    all_rows = np.concatenate(shards)
    assert {decode_tokens(r) for r in all_rows} == {decode_tokens(r) for r in full}
    # per-host skip: global skip 4 -> each host skips 2 of its own stream
    s0 = np.concatenate(list(it_fn(seq_len=16, batch_size=2,
                                   process_count=2, process_index=0, skip=4)))
    np.testing.assert_array_equal(s0, shards[0][2:])


def test_misaligned_skip_resumes_exactly(tfrecord_dir):
    """An epoch-boundary wrap can checkpoint a cursor with
    ``skip % process_count != 0``; the per-host ceil arithmetic must still
    resume at exactly record ``skip`` (union across hosts, order-free)."""
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    full = np.concatenate(list(it_fn(seq_len=16, batch_size=4)))
    for skip in (1, 3, 5):
        shards = [
            np.concatenate(list(it_fn(seq_len=16, batch_size=1,
                                      process_count=2, process_index=i,
                                      skip=skip)))
            for i in range(2)
        ]
        got = {decode_tokens(r) for r in np.concatenate(shards)}
        want = {decode_tokens(r) for r in full[skip:]}
        assert got == want, f"skip={skip}"
        # and nothing before the cursor leaks back in
        assert not ({decode_tokens(r) for r in full[:skip]} & got)


def test_loop_repeats(tfrecord_dir):
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    it = it_fn(seq_len=16, batch_size=16, loop=True)
    seen = 0
    for batch in it:
        seen += batch.shape[0]
        if seen > 40:  # corpus is 20; looping proven
            break
    assert seen > 40


def test_loop_ragged_corpus_always_full_batches(tfrecord_dir):
    """corpus 20 % batch 8 != 0: looping batches must ALL be full (static
    shape for jit) and straddle the corpus boundary without dropping or
    duplicating records."""
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    ordered = np.concatenate(list(it_fn(seq_len=16, batch_size=4)))  # 20 rows
    it = it_fn(seq_len=16, batch_size=8, loop=True)
    batches = [next(it) for _ in range(5)]  # 40 rows = 2 full passes
    assert all(b.shape == (8, 17) for b in batches)
    got = np.concatenate(batches)
    np.testing.assert_array_equal(got[:20], ordered)
    np.testing.assert_array_equal(got[20:40], ordered)  # second pass intact


def test_shuffle_buffer_permutes_but_preserves_records(tfrecord_dir):
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    plain = np.concatenate(list(it_fn(seq_len=16, batch_size=4)))
    shuffled = np.concatenate(list(
        it_fn(seq_len=16, batch_size=4, shuffle_buffer=8, seed=1)))
    # same multiset of records, different order, deterministic per seed
    assert {decode_tokens(r) for r in shuffled} == {
        decode_tokens(r) for r in plain}
    assert not np.array_equal(shuffled, plain)
    again = np.concatenate(list(
        it_fn(seq_len=16, batch_size=4, shuffle_buffer=8, seed=1)))
    np.testing.assert_array_equal(shuffled, again)


def test_shuffled_resume_is_deterministic(tfrecord_dir):
    """Interrupting and resuming a SHUFFLED run must replay the
    uninterrupted run's record order exactly: the cursor skip applies to
    the seeded shuffle's output, not its input."""
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    kw = dict(seq_len=16, batch_size=4, shuffle_buffer=8, seed=5)
    full = np.concatenate(list(it_fn(**kw)))
    # "interrupt" after 2 batches (8 records), resume from the cursor
    resumed = np.concatenate(list(it_fn(skip=8, **kw)))
    np.testing.assert_array_equal(resumed, full[8:])
    # and at a cursor that is not a batch multiple (batch-size change)
    resumed2 = np.concatenate(list(it_fn(skip=5, **kw)))
    np.testing.assert_array_equal(resumed2, full[5:])


def test_shuffled_resume_multihost_matches_uninterrupted(tfrecord_dir):
    """Same guarantee per host under round-robin sharding: each host's
    resumed shuffled stream continues its own uninterrupted order."""
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    for idx in range(2):
        kw = dict(seq_len=16, batch_size=2, process_count=2,
                  process_index=idx, shuffle_buffer=4, seed=3)
        full = np.concatenate(list(it_fn(**kw)))
        # global cursor 8 -> this host consumed 4 of its own stream
        resumed = np.concatenate(list(it_fn(skip=8, **kw)))
        np.testing.assert_array_equal(resumed, full[4:])


def test_shuffled_loop_resume_continues_stream(tfrecord_dir):
    """Under loop=True (the trainer's mode) the shuffled stream is
    infinite; a resumed iterator must produce the same continuation."""
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    kw = dict(seq_len=16, batch_size=4, loop=True, shuffle_buffer=8, seed=7)
    it = it_fn(**kw)
    full = np.concatenate([next(it) for _ in range(10)])
    it2 = it_fn(skip=12, **kw)
    resumed = np.concatenate([next(it2) for _ in range(7)])
    np.testing.assert_array_equal(resumed, full[12:])


def test_loop_skip_records_reappear_on_wrap(tfrecord_dir):
    """Resume-skipped records must come back after a full cycle (the
    reference's repeat-after-skip loses them permanently, data.py:54-62)."""
    _, it_fn = iterator_from_tfrecords_folder(str(tfrecord_dir), "train")
    ordered = np.concatenate(list(it_fn(seq_len=16, batch_size=4)))
    it = it_fn(seq_len=16, batch_size=4, loop=True, skip=6)
    rows = np.concatenate([next(it) for _ in range(6)])  # 24 rows
    np.testing.assert_array_equal(rows[:14], ordered[6:])   # records 6..19
    np.testing.assert_array_equal(rows[14:20], ordered[:6])  # 0..5 reappear
