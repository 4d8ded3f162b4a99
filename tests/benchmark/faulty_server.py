"""``benchmark/server.py`` with the timed path broken underneath: every fifth
token is altered where it is produced. Started by the tests in place of the
server, to see ``correct`` come out false."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import builders, server  # noqa: E402


def alter(outputs, count):
    if count % 5 == 3:
        outputs = dict(outputs)
        outputs["NEXT_TOKEN"] = (outputs["NEXT_TOKEN"] + 1) % 7
    return outputs


def break_tokens(model):
    execute, decoupled = model.execute, model.execute_decoupled
    produced = {"n": 0}

    def faulty_execute(inputs, parameters):
        produced["n"] += 1
        return alter(execute(inputs, parameters), produced["n"])

    def faulty_decoupled(inputs, parameters):
        for n, outputs in enumerate(decoupled(inputs, parameters)):
            yield alter(outputs, n)

    model.execute = faulty_execute
    if model.decoupled:
        model.execute_decoupled = faulty_decoupled


_resolve = builders.resolve


def resolve(name):
    def build(config, seed, **args):
        model, decoder = _resolve(name)(config, seed, **args)
        break_tokens(model)
        return model, decoder
    return build


builders.resolve = resolve

if __name__ == "__main__":
    sys.exit(server.main())
