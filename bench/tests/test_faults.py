"""Each fault the one-chip served path can have turns ``correct`` false.

``run_cell`` runs the tiny cell with the decode dispatch broken underneath
it: every token altered where it is produced, a step that returns its
state unchanged, and half of the slots left out (their tokens copied from
the other half)."""

import pytest

from bench.tests.conftest import TINY_GAP_LIMIT
from bench.tests.test_run import run_tiny, short_fuse


def _broken(kind):
    def hook(eng):
        short_fuse(eng)
        decode_for = eng._decode_for
        vocab = eng.cfg.vocab_size

        def broken_for(k):
            fn = decode_for(k)

            def broken(pp, st, toks):
                seq, st2 = fn(pp, st, toks)
                if kind == "token_altered":
                    seq = (seq + 1) % vocab
                elif kind == "state_unchanged":
                    st2 = st
                elif kind == "half_batch":
                    half = seq.shape[1] // 2
                    seq = seq.at[:, half:].set(seq[:, :half])
                return seq, st2
            return broken

        eng._decode_for = broken_for
    return hook


@pytest.mark.parametrize("kind", ["token_altered", "state_unchanged",
                                  "half_batch"])
def test_a_broken_decode_step_is_not_correct(conf, kind):
    res = run_tiny(conf, hook=_broken(kind))
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > TINY_GAP_LIMIT


