"""One seeded generator for every traffic mix under ``bench/traffic/``.

A mix is a JSON file of parameters; this module is the only code that reads
them, so a new mix is a new data file.  The harness drives every mix as a
closed loop with one client per serving slot (``harness.Driver``).  Keys:

``prompt_tokens``    ``{"lo", "hi"}``: uniform prompt length.
``output_tokens``    ``{"lo", "hi"}``: uniform token budget (EOS is off).
``sizes``            how many (prompt, output) pairs make the mix's fixed
                     sequence of sizes.
``sizes_seed``       the seed of that sequence.

The sequence of sizes is the same for every run seed: the seed draws the
token ids only.  A window sees a few passes of the loop, so sizes drawn
per seed would change the work it holds; with one sequence every seed's
window holds the same work.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


@dataclasses.dataclass
class Spec:
    """One request as the generator drew it, before the program sees it."""
    uid: int
    prompt: np.ndarray             # (S,) int32
    max_new_tokens: int


def size_set(mix: dict) -> np.ndarray:
    """(sizes, 2) int: prompt tokens, output tokens."""
    rng = np.random.default_rng(mix["sizes_seed"])
    n = mix["sizes"]
    pr, out = mix["prompt_tokens"], mix["output_tokens"]
    prompt = rng.integers(pr["lo"], pr["hi"] + 1, n)
    budget = rng.integers(out["lo"], out["hi"] + 1, n)
    return np.stack([prompt, budget], 1)


class Traffic:
    """The request stream of one run: ``next()`` gives the next request."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.order = size_set(mix)
        self.n = 0

    def next(self) -> Spec:
        length, budget = (int(v) for v in
                          self.order[self.n % len(self.order)])
        toks = self.rng.integers(0, self.vocab, length).astype(np.int32)
        spec = Spec(uid=self.n, prompt=toks, max_new_tokens=budget)
        self.n += 1
        return spec
