#!/usr/bin/env python
"""TPU shared-memory inference over HTTP (the cudashm example, TPU-native).

The SERVER's process owns the chip; this client is numpy-only and never
imports jax (a TPU belongs to one process at a time). It writes the
regions' host windows, hands the server their raw handles, and the server
moves the bytes onto its device and back: no tensor bytes ride the request.
The zero-copy ``jax.Array`` handover (``set_shared_memory_region_from_jax``,
``colocated=True``) is for a client that shares the server's process;
``chip_smoke.py`` drives that arm.
"""

import argparse
import sys

import numpy as np

import client_tpu.http as httpclient
import client_tpu.utils.tpu_shared_memory as tpushm


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-u", "--url", default="localhost:8000")
    args = parser.parse_args()

    with httpclient.InferenceServerClient(args.url) as client:
        client.unregister_tpu_shared_memory()
        a = np.arange(16, dtype=np.int32).reshape(1, 16)
        b = np.ones((1, 16), np.int32)
        nbytes = 64

        rin = tpushm.create_shared_memory_region("input_data", 2 * nbytes)
        rout = tpushm.create_shared_memory_region("output_data", 2 * nbytes)
        tpushm.set_shared_memory_region(rin, [a, b])  # back to back
        client.register_tpu_shared_memory("input_data", tpushm.get_raw_handle(rin), 0, 2 * nbytes)
        client.register_tpu_shared_memory("output_data", tpushm.get_raw_handle(rout), 0, 2 * nbytes)

        inputs = [
            httpclient.InferInput("INPUT0", [1, 16], "INT32"),
            httpclient.InferInput("INPUT1", [1, 16], "INT32"),
        ]
        inputs[0].set_shared_memory("input_data", nbytes)
        inputs[1].set_shared_memory("input_data", nbytes, offset=nbytes)
        outputs = [
            httpclient.InferRequestedOutput("OUTPUT0"),
            httpclient.InferRequestedOutput("OUTPUT1"),
        ]
        outputs[0].set_shared_memory("output_data", nbytes)
        outputs[1].set_shared_memory("output_data", nbytes, offset=nbytes)

        client.infer("simple", inputs, outputs=outputs)
        sums = tpushm.get_contents_as_numpy(rout, "INT32", [1, 16])
        diffs = tpushm.get_contents_as_numpy(rout, "INT32", [1, 16], offset=nbytes)
        ok = bool((sums == a + b).all() and (diffs == a - b).all())
        del sums, diffs  # views over the mapping: drop before unmapping
        assert "jax" not in sys.modules

        client.unregister_tpu_shared_memory()
        tpushm.destroy_shared_memory_region(rin)
        tpushm.destroy_shared_memory_region(rout)
        if not ok:
            sys.exit("http tpu shm error: incorrect results")
        print("PASS: http tpu shared memory")


if __name__ == "__main__":
    main()
