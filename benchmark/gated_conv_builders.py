"""The gated-convolution family's builder: the program's decoder at a
configuration's sizes behind the program's stream model.

``generate`` returns ``(model, decoder)`` as every builder does
(``benchmark/family.py``). The decoder is built with ``seed=None``: its
``_params`` are ``jax.ShapeDtypeStruct`` and no weight is allocated before
the benchmark draws its own from the seed. The round, the slot prefill, the
table of slots and the stream loop are the program's own, untouched.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def generate(config: Dict[str, Any], seed: int, **args) -> Tuple[Any, Any]:
    from client_tpu.models.gated_conv_decoder import GatedConvDecoderModel
    from client_tpu.models.generate import TinyGenerateModel

    decoder = GatedConvDecoderModel(config, seed=None)
    return TinyGenerateModel(decoder=decoder, **args), decoder
