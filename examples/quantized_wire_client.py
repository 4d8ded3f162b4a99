"""INT8-quantized wire transport: 4x less bandwidth for FP32 tensors.

The client quantizes an FP32 tensor on-device (Pallas ``quantize_int8``),
ships INT8 bytes over the wire, and dequantizes the response — the classic
bandwidth play for WAN/DCN hops, impossible to express in the reference
client without custom model logic (here it is two client-side ops).

The two ops run on THIS process's JAX device. A TPU belongs to one process
at a time, so where the server holds the only chip run this client with
``JAX_PLATFORMS=cpu`` (the kernels then run in interpret mode).

Usage: quantized_wire_client.py [-u HOST:PORT]
"""

import argparse
import sys

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-u", "--url", default="127.0.0.1:8000")
    args = parser.parse_args()

    import client_tpu.http as httpclient
    from client_tpu.ops import dequantize_int8, quantize_int8

    rng = np.random.default_rng(11)
    x = rng.standard_normal((1, 8192)).astype(np.float32)
    scale = float(np.abs(x).max() / 127.0)

    with httpclient.InferenceServerClient(args.url) as client:
        q = np.asarray(quantize_int8(x, scale))  # 4 bytes -> 1 byte per elem
        inp = httpclient.InferInput("INPUT0", list(q.shape), "INT8")
        inp.set_data_from_numpy(q)
        result = client.infer("identity_int8", [inp])
        q_back = result.as_numpy("OUTPUT0")
        restored = np.asarray(dequantize_int8(q_back, scale))

    err = np.abs(restored - x).max()
    wire_bytes = q.nbytes
    full_bytes = x.nbytes
    print(f"wire payload {wire_bytes} B vs {full_bytes} B fp32 ({full_bytes / wire_bytes:.0f}x smaller)")
    print(f"max dequantization error {err:.6f} (half-step bound {scale / 2:.6f})")
    if err > scale / 2 + 1e-6:
        print("FAIL: dequantization error beyond the quantization step")
        return 1
    print("PASS: quantized_wire_client")
    return 0


if __name__ == "__main__":
    sys.exit(main())
