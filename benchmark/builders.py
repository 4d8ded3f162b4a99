"""How a cell's served model is built from its configuration file.

The program's decoder reads its five sizes from class attributes
(``client_tpu/models/decoder.py``), so a subclass with a configuration's
sizes is the whole seam: the step, the cache, the batcher and the generate
loop are the program's own, untouched. A cell file names a builder of this
module (or ``"<module>:<function>"`` of a later one) and its arguments.

Every builder returns ``(model, decoder)``: the model ``ServerCore`` serves
and the decoder whose ``_params`` the benchmark replaces with weights it made
on the device from the seed. Only their shapes and types are read, so a
builder may leave ``_params`` as a tree of ``jax.ShapeDtypeStruct``
(``benchmark/family.py``); the two here hand over zeros made on the device,
which ``server.Served.reseed`` lets go of before it draws.

The two builders here are the GPT-2 family's: they read the configuration
through ``shapes.sizes``. A model of another family brings its builder as a
new module beside its arithmetic and reference (``benchmark/family.py``).
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, Tuple

import numpy as np

from . import shapes


class NoDraw(np.random.Generator):
    """A generator that draws nothing. The program draws its weights on the
    host with ``np.random.default_rng(seed)``, 23 to 46 s at these sizes, and
    the benchmark then puts its own in their place; ``default_rng`` hands a
    ``Generator`` back as it is, so this one gives the program zeros that are
    made on the device, of the shapes and through the casts it asks for."""

    def __init__(self):
        super().__init__(np.random.PCG64(0))

    def standard_normal(self, size=None, dtype=np.float32, out=None):
        import jax.numpy as jnp

        return jnp.zeros(size, jnp.float32)


def build_decoder(config: Dict[str, Any], seed: int, **args):
    """The program's decoder at the configuration's sizes, built."""
    from client_tpu.models.decoder import TinyDecoderModel

    s = shapes.sizes(config)
    cls = type("ConfiguredDecoder", (TinyDecoderModel,), {
        "VOCAB": s["vocab"], "D_MODEL": s["d_model"], "HEADS": s["heads"],
        "LAYERS": s["layers"], "MAX_LEN": s["max_len"]})
    decoder = cls(seed=NoDraw(), **args)
    decoder._ensure_built()
    return decoder


def tiny_lm_generate(config: Dict[str, Any], seed: int, **args) -> Tuple[Any, Any]:
    from client_tpu.models.generate import TinyGenerateModel

    decoder = build_decoder(config, seed, **args)
    return TinyGenerateModel(decoder=decoder), decoder


def decoder_lm_batched(config: Dict[str, Any], seed: int,
                       attention_impl: str = "einsum", **args) -> Tuple[Any, Any]:
    from client_tpu.models.decoder_batched import BatchedDecoderModel

    model = BatchedDecoderModel(seed=seed, attention_impl=attention_impl, **args)
    # the batcher composes its decoder and builds lazily: nothing has been
    # drawn or compiled from the fixture it made for itself
    model._decoder = build_decoder(config, seed, attention_impl=attention_impl)
    return model, model._decoder


def resolve(name: str) -> Callable[..., Tuple[Any, Any]]:
    if ":" in name:
        module, function = name.split(":", 1)
        return getattr(importlib.import_module(module), function)
    if name not in ("tiny_lm_generate", "decoder_lm_batched"):
        raise KeyError(f"no builder {name!r}")
    return globals()[name]
