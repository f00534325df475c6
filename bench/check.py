"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the engine is freed, a sample of the
requests the window served (finished, or still in a slot at its close with
the tokens served so far), drawn from the run's seed, is run through the
float32 reference (``reference.py`` of the model's family under
``bench/models``) over each prompt with its served tokens.  The number
compared is the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.  Its limit is the
configuration's ``check.max_logit_gap`` (PERF.md gives the readings it was
set from).
"""

from __future__ import annotations

from typing import List

import numpy as np

from bench import models


def sample(finished: List, seed: int) -> List:
    """The longest served request, and one drawn from the seed among those
    each serving slot served: every slot's output is checked."""
    if not finished:
        return []
    key = lambda r: (len(r.prompt) + len(r.tokens), r.uid)
    longest = max(finished, key=key)
    rng = np.random.default_rng(seed)
    picked = {longest.uid: longest}
    for slot in sorted({r.slot for r in finished}, key=str):
        mine = sorted((r for r in finished if r.slot == slot), key=key)
        r = mine[rng.integers(len(mine))]
        picked.setdefault(r.uid, r)
    return sorted(picked.values(), key=key, reverse=True)


def gaps(conf: dict, seed: int, reqs: List, quant=None) -> List[float]:
    """Widest reference-logit gap of each request's served tokens (with
    ``quant``: of the tokens the lower-precision control puts first)."""
    fam = models.family(conf)
    m = fam.Dims.of(conf)
    w = fam.weights.make(m, seed)
    return [fam.reference.widest_gap(m, w, r.prompt, np.asarray(r.tokens),
                                     quant=quant) for r in reqs]
