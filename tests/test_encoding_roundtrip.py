"""Property tests: every registered codec round-trips every task (§4.3).

``encode`` then ``decode`` must reproduce the task bit-for-bit — mask,
partial solution and depth — for EVERY codec in ``encoding.CODECS``, over
randomized instance sizes and record schemas (including schemas with extra
payload fields, i.e. ``pad_words > 0``).  The byte-accounting identities
the benchmarks quote (``record_words``/``record_bytes``/``pad_words``)
are pinned against the schema arithmetic at the same time, so the wire
sizes in EXPERIMENTS can never drift from the implementation.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.encoding import (
    CODECS,
    DEFAULT_RECORD_FIELDS,
    Task,
    make_codec,
    resolve_record_words,
)
from repro.graphs.bitgraph import n_words
from repro.graphs.generators import erdos_renyi


class _Problem:
    """A stand-in plugin carrying only the record schema."""

    def __init__(self, fields):
        self.record_fields = tuple(fields)


# schema menu: the native triple alone, plus variants with extra payload
# words (a literal-width scalar, a bitset, and an adjacency-sized blob) —
# the shapes that exercise pad_words = 0, small, W-sized and n·W-sized
_EXTRA_FIELDS = st.sampled_from(
    [
        (),
        (("score", 1),),
        (("bound", 2), ("tiebreak", 1)),
        (("aux_mask", "W"),),
        (("blob", "n*W"),),
        (("score", 1), ("aux_mask", "W")),
    ]
)


def _random_task(rng, n, W):
    mask_bits = rng.randint(0, 2**n - 1)
    # the partial solution is a subset of the OUT-of-instance vertices in
    # real traffic, but the codecs must not care: draw it independently
    sol_bits = rng.randint(0, 2**n - 1)

    def pack(bits):
        words = np.zeros(W, np.uint32)
        for w in range(W):
            words[w] = (bits >> (32 * w)) & 0xFFFFFFFF
        return words

    return Task(
        mask=pack(mask_bits), sol_mask=pack(sol_bits), depth=rng.randint(0, n)
    )


@settings(max_examples=30, deadline=None)
@given(
    st.sampled_from(sorted(CODECS)),
    st.integers(1, 70),
    _EXTRA_FIELDS,
    st.integers(0, 2**31),
)
def test_codec_roundtrip_bit_exact(name, n, extra, seed):
    import random

    rng = random.Random(seed)
    W = n_words(n)
    fields = DEFAULT_RECORD_FIELDS + tuple(extra)
    codec = make_codec(name, n, problem=_Problem(fields))
    g = erdos_renyi(n, 0.4, seed % 1000)
    task = _random_task(rng, n, W)

    rec = codec.encode(task, g) if name == "basic" else codec.encode(task)
    assert rec.dtype == np.uint32 and rec.shape == (codec.record_words,)

    back = codec.decode(rec, g)
    assert (back.mask == task.mask).all()
    assert (back.sol_mask == task.sol_mask).all()
    assert back.depth == task.depth

    # byte accounting: record_words is the schema arithmetic exactly
    want = resolve_record_words(fields, n, W)
    if name == "basic":
        want += n * W  # adjacency rows ride on top of the schema
    assert codec.record_words == want
    assert codec.record_bytes == 4 * want
    assert codec.pad_words == codec.record_words - codec.native_words
    if name == "optimized" and not extra:
        assert codec.pad_words == 0


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(sorted(CODECS)), st.integers(1, 70))
def test_codec_depth_word_survives_extremes(name, n):
    """Depth is carried in a u32 word: 0 and the deepest possible value
    (n, a leaf) must both survive, for every codec and width class."""
    W = n_words(n)
    codec = make_codec(name, n)
    g = erdos_renyi(n, 0.3, 1)
    for depth in (0, n):
        t = Task(
            mask=np.full(W, 0xFFFFFFFF, np.uint32),
            sol_mask=np.zeros(W, np.uint32),
            depth=depth,
        )
        rec = codec.encode(t, g) if name == "basic" else codec.encode(t)
        assert codec.decode(rec, g).depth == depth
