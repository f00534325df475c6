"""One package per model family, found by the configuration's ``model_type``.

``bench/models/<model_type>/`` holds what belongs to one family, so that a
configuration of a new family is a new package and a new file under
``bench/configs``, with no edit to the harness:

``model_config(conf)``   the program's ``ModelConfig`` for the configuration.
``Dims.of(conf)``        the shapes that the reference, the weight layout and
                         ``bench/flops.py`` read.
``weights``              ``make(dims, seed)``: the reference's seeded weights
                         in the published layout; ``program_params(dims,
                         vocab_padded, seed)``: the same weights in the
                         program's parameter tree.
``reference``            ``widest_gap(dims, w, prompt, served, quant=None)``:
                         the plain float32 forward, independent of the
                         program, and the number ``correct`` compares.
"""

from __future__ import annotations

import importlib


def family(conf: dict):
    """The package of ``conf``'s model family."""
    name = conf["model_type"]
    try:
        return importlib.import_module(f"bench.models.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.models.{name}":
            raise
        raise ValueError(f"no model family {name!r} under bench/models"
                         ) from None
