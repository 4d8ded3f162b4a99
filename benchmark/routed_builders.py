"""The routed family's builder: the program's routed decoder at a
configuration's sizes behind the program's stream model.

``generate`` returns ``(model, decoder)`` as every builder does
(``benchmark/family.py``). The decoder is built with ``seed=None``: its
``_params`` are ``jax.ShapeDtypeStruct`` and no weight is allocated before
the benchmark draws its own from the seed. The step, the prefill, the caches
and the stream loop are the program's own, untouched.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def generate(config: Dict[str, Any], seed: int, **args) -> Tuple[Any, Any]:
    from client_tpu.models.generate import TinyGenerateModel
    from client_tpu.models.routed_decoder import RoutedDecoderModel

    decoder = RoutedDecoderModel(config, seed=None, **args)
    return TinyGenerateModel(decoder=decoder), decoder
