"""What a configuration's family brings, and the one place it is looked up.

A configuration file may name two modules, as ``cells/*.json`` names a
builder:

    "arithmetic": "<module>",   absent: ``benchmark.shapes``
    "reference":  "<module>",   absent: ``benchmark.reference``

``benchmark.shapes`` and ``benchmark.reference`` are the GPT-2 family's. A
model that is not GPT-2's brings its own two modules (and its builder, its
configuration and its cells) as new files; ``run.py``, ``server.py``,
``calibrate.py`` and ``layer_metrics/*.py`` ask the modules resolved here
and nothing else, and read no key of a configuration but these two.

The contract, all of it:

arithmetic (imports no jax: the users' process loads it)
    ``vocab(config)``: the ids traffic draws from (the slice held, where a
    vocabulary is sliced).
    ``max_len(config)``: the positions a sequence may reach.
    ``work(config, positions)``: totals over the 0-based positions of the
    tokens processed in the window; a dict with at least
    ``tokens_processed`` and ``flops``, and whatever ``step_least`` reads.
    ``step_least(config, work, width)``: ``{"bytes", "flops"}`` of a mean
    step of ``width`` sequences: the least bytes the chip must move and the
    FLOPs the model needs, the same count whatever implements the step (for
    routed experts the experts its tokens reach, not those held).
    ``total_params(config)``: as the served code lays them out, and of what
    is held here where the configuration is cut (below).
    ``init_scale(path, leaf)``, optional: the standard deviation
    ``server.make_params`` draws a leaf at, or ``(mean, deviation)``;
    ``path`` is the leaf's keys as strings, and of ``leaf`` only ``shape``
    and ``dtype`` may be read (it may be a ``jax.ShapeDtypeStruct``).
    ``None``, or no such function, keeps the rule the GPT-2 family is drawn
    by (0.02 for the tables and the head, ``shape[0] ** -0.5`` for the
    rest): stacked experts ``[experts, d, f]`` need a rule of their own.
    ``fixture(config)``, optional: ``(configuration, limits)`` for the CPU
    tests (``tests/benchmark/benchmark_fixture.py``): the family's
    configuration at fixture size (a few layers of every kind it has, tens
    of units wide, a few hundred ids, 64 positions or more, naming the same
    ``arithmetic`` and ``reference``) and the limits every cell of the
    family is held to there, by the names its cell files use. Each cell of
    ``BENCHMARK.json`` is then run on the CPU through its own builder,
    arithmetic, reference and control at that size, with no edit to the
    tests. No such function: the GPT-2 family's fixture and
    ``{"served_gap_max": 0.01}``.

reference (imports nothing of the program; takes the weights as data)
    ``served_token_gaps(params, config, sessions, length, control=False)``:
    ``{"positions", "served_gap_max"}`` and, with ``control``, the same gap
    for the tokens the pass in the nearest lower precision puts first:
    ``control_gap_max``. ``calibrate.py`` holds every family's limit between
    a sound run and that control; a reference without it fails there by
    name. Every other number it returns is a reading of the family's own
    (say the share of positions it set aside because two router scores tie
    within rounding): ``run.py`` and ``calibrate.py`` hand each to ``judge``
    by its name, a limit named for it in ``cells/<cell>.json`` holds it like
    ``served_gap_max`` and prints it under ``compared``, and a limit whose
    reading is absent is not met. The control is judged with its gap in the
    served gap's place and those other readings as they stood.

builder (``cells/<cell>.json``: ``"<module>:<function>"``)
    ``builder(config, seed, **args)`` returns ``(model, decoder)``: the model
    ``ServerCore`` serves and the object whose ``_params`` are the weights.
    After ``decoder._ensure_built()`` the tree at ``decoder._params`` is the
    template: ``server.Served`` takes each leaf's ``shape`` and ``dtype``
    from it once, and before ``model._ensure_built()`` puts weights drawn
    from the seed there (``reseed``: what was there is let go of first, so
    the chip holds one copy, as long as nothing else keeps a reference). The builder may leave that tree as
    ``jax.ShapeDtypeStruct``; ``decoder._ensure_built()`` must then allocate
    no weight. The step must read ``decoder._params`` at each call.

a configuration that is cut to the chip's share (``model-configs`` section 4)
    ``"reduced"``, in the file and in ``BENCHMARK.json`` alike, lists the keys
    changed from the source. Where it is not empty the file also carries
    ``"published"`` (for each such key the source's own value; where both are
    numbers the file's is the smaller) and ``"deployment"`` (a sentence: over
    how many chips each layer is divided, how, and which layers are kept),
    and ``total_params`` counts what is held, not what is published.
    ``tests/benchmark`` holds every configuration to that.
"""

from __future__ import annotations

import importlib
import inspect
from types import ModuleType
from typing import Any, Dict

ARITHMETIC = ("vocab", "max_len", "work", "step_least", "total_params")


def _module(config: Dict[str, Any], key: str, default: str, needs) -> ModuleType:
    name = config.get(key, default)
    module = importlib.import_module(name)
    lacking = [f for f in needs if not callable(getattr(module, f, None))]
    if lacking:
        raise AttributeError(
            f"the configuration's {key} module {name!r} lacks {', '.join(lacking)}")
    return module


def arithmetic(config: Dict[str, Any]) -> ModuleType:
    return _module(config, "arithmetic", "benchmark.shapes", ARITHMETIC)


def reference(config: Dict[str, Any], control: bool = False) -> ModuleType:
    """The family's reference; with ``control``, one that has the control."""
    module = _module(config, "reference", "benchmark.reference",
                     ("served_token_gaps",))
    if control and "control" not in inspect.signature(
            module.served_token_gaps).parameters:
        raise AttributeError(
            f"the configuration's reference module {module.__name__!r} has no "
            "control: its served_token_gaps takes no 'control'")
    return module
