#!/usr/bin/env python
"""TPU shared-memory inference over GRPC — the cudashm example, TPU-native.

Equivalent of the reference's simple_grpc_cudashm_client.py with the CUDA IPC
region replaced by a tpu_shared_memory region.

The SERVER's process owns the chip; this client is numpy-only and never
imports jax (a TPU belongs to one process at a time). It writes the
regions' host windows, hands the server their raw handles, and the server
moves the bytes onto its device and back. Binding live ``jax.Array``s
(``set_shared_memory_region_from_jax``, ``colocated=True``) is for a client
that shares the server's process; ``chip_smoke.py`` drives that arm.
"""

import argparse
import sys

import numpy as np

import client_tpu.grpc as grpcclient
import client_tpu.utils.tpu_shared_memory as tpushm


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-u", "--url", default="localhost:8001")
    args = parser.parse_args()

    with grpcclient.InferenceServerClient(args.url) as client:
        client.unregister_tpu_shared_memory()

        input0_data = np.arange(16, dtype=np.int32).reshape(1, 16)
        input1_data = np.ones((1, 16), np.int32)
        nbytes = 64

        shm_ip = tpushm.create_shared_memory_region("input_data", nbytes * 2)
        tpushm.set_shared_memory_region(shm_ip, [input0_data, input1_data])
        client.register_tpu_shared_memory(
            "input_data", tpushm.get_raw_handle(shm_ip), 0, nbytes * 2
        )
        shm_op = tpushm.create_shared_memory_region("output_data", nbytes * 2)
        client.register_tpu_shared_memory(
            "output_data", tpushm.get_raw_handle(shm_op), 0, nbytes * 2
        )

        inputs = [
            grpcclient.InferInput("INPUT0", [1, 16], "INT32"),
            grpcclient.InferInput("INPUT1", [1, 16], "INT32"),
        ]
        inputs[0].set_shared_memory("input_data", nbytes)
        inputs[1].set_shared_memory("input_data", nbytes, offset=nbytes)
        outputs = [
            grpcclient.InferRequestedOutput("OUTPUT0"),
            grpcclient.InferRequestedOutput("OUTPUT1"),
        ]
        outputs[0].set_shared_memory("output_data", nbytes)
        outputs[1].set_shared_memory("output_data", nbytes, offset=nbytes)

        client.infer("simple", inputs, outputs=outputs)

        # host-window read: the server wrote its results into the region
        output0 = tpushm.get_contents_as_numpy(shm_op, "INT32", [1, 16])
        output1 = tpushm.get_contents_as_numpy(shm_op, "INT32", [1, 16], offset=nbytes)
        ok = bool((output0 == input0_data + input1_data).all()
                  and (output1 == input0_data - input1_data).all())
        del output0, output1  # views over the mapping: drop before unmapping
        if not ok:
            sys.exit("tpu shm infer error: incorrect results")
        assert "jax" not in sys.modules

        print(client.get_tpu_shared_memory_status())
        client.unregister_tpu_shared_memory()
        tpushm.destroy_shared_memory_region(shm_ip)
        tpushm.destroy_shared_memory_region(shm_op)
        print("PASS: tpu shared memory")


if __name__ == "__main__":
    main()
