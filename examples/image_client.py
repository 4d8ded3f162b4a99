#!/usr/bin/env python
"""Image classification client driven by model metadata.

Equivalent of the reference's image_client.py (parse_model :60, preprocess
:154 with NONE/INCEPTION/VGG scaling :174-176, postprocess :196,
HTTP/GRPC/async switches :262-510) — with the preprocessing running through
XLA (client_tpu.ops Pallas normalize kernel) instead of numpy/PIL math.

The preprocessing runs on THIS process's JAX device. A TPU belongs to one
process at a time, so where the server holds the only chip run this client
with ``JAX_PLATFORMS=cpu`` (the kernel then runs in interpret mode), or use
ensemble_image_client.py, which leaves the preprocessing to the server.

Works against the bundled densenet_onnx flax model
(``python -m client_tpu.serve --vision``) or a real tritonserver hosting the
densenet_onnx fixture. Input images: .npy arrays (HWC uint8) or, when Pillow
is available, any image file.
"""

import argparse
import sys

import numpy as np


def parse_model(metadata, config):
    """Pull the single input/output contract out of model metadata."""
    if len(metadata["inputs"]) != 1 or len(metadata["outputs"]) != 1:
        sys.exit("expecting a single-input single-output vision model")
    inp = metadata["inputs"][0]
    out = metadata["outputs"][0]
    shape = [d for d in inp["shape"] if d != -1]
    if len(shape) == 3 and shape[0] in (1, 3):
        fmt, c, h, w = "CHW", shape[0], shape[1], shape[2]
    elif len(shape) == 3:
        fmt, h, w, c = "HWC", shape[0], shape[1], shape[2]
    else:
        sys.exit(f"unexpected input shape {inp['shape']}")
    return inp["name"], out["name"], fmt, c, h, w, inp["datatype"]


def load_image(path, h, w):
    if path.endswith(".npy"):
        img = np.load(path)
    else:
        try:
            from PIL import Image
        except ImportError:
            sys.exit("non-.npy images need Pillow; pass a .npy HWC uint8 array")
        img = np.asarray(Image.open(path).convert("RGB").resize((w, h)))
    if img.shape[:2] != (h, w):
        # nearest-neighbor resize without PIL
        ys = (np.linspace(0, img.shape[0] - 1, h)).astype(int)
        xs = (np.linspace(0, img.shape[1] - 1, w)).astype(int)
        img = img[ys][:, xs]
    return img.astype(np.float32)


def preprocess(img, fmt, dtype, scaling):
    """Scaling modes from the reference, fused on-device via the Pallas op."""
    from client_tpu.ops import normalize_image

    if scaling == "INCEPTION":
        arr = np.asarray(normalize_image(img, scale=2.0 / 255.0, shift=-1.0, out_dtype=np.float32))
    elif scaling == "VGG":
        arr = img[..., ::-1] - np.array([123.68, 116.779, 103.939], dtype=np.float32)
    else:
        arr = np.asarray(normalize_image(img, scale=1.0, shift=0.0, out_dtype=np.float32))
    if fmt == "CHW":
        arr = np.transpose(arr, (2, 0, 1))
    return np.ascontiguousarray(arr, dtype=np.float32)


def postprocess(result, output_name, topk):
    entries = result.as_numpy(output_name)
    if entries is None:
        sys.exit("no classification output returned")
    for entry in entries.reshape(-1)[:topk]:
        value, idx, *label = entry.decode().split(":")
        name = label[0] if label else idx
        print(f"    {float(value):.6f} ({idx}) = {name}")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("image", nargs="?", default=None, help=".npy or image file")
    parser.add_argument("-m", "--model-name", default="densenet_onnx")
    parser.add_argument("-u", "--url", default=None)
    parser.add_argument("-i", "--protocol", choices=("http", "grpc"), default="http")
    parser.add_argument("-c", "--classes", type=int, default=3)
    parser.add_argument(
        "-s", "--scaling", choices=("NONE", "INCEPTION", "VGG"), default="INCEPTION"
    )
    parser.add_argument("-a", "--async_run", action="store_true")
    args = parser.parse_args()

    if args.protocol == "http":
        import client_tpu.http as clientmod

        url = args.url or "localhost:8000"
    else:
        import client_tpu.grpc as clientmod

        url = args.url or "localhost:8001"

    kwargs = {"network_timeout": 300.0} if args.protocol.lower() == "http" else {}
    with clientmod.InferenceServerClient(url, **kwargs) as client:
        metadata = client.get_model_metadata(args.model_name)
        config = client.get_model_config(args.model_name)
        input_name, output_name, fmt, c, h, w, dtype = parse_model(metadata, config)

        if args.image:
            img = load_image(args.image, h, w)
        else:
            print("no image supplied; classifying random noise")
            img = np.random.default_rng(0).uniform(0, 255, (h, w, c)).astype(np.float32)

        data = preprocess(img, fmt, dtype, args.scaling)
        inp = clientmod.InferInput(input_name, list(data.shape), dtype)
        inp.set_data_from_numpy(data)
        outputs = [clientmod.InferRequestedOutput(output_name, class_count=args.classes)]

        if args.async_run:
            handle = client.async_infer(args.model_name, [inp], outputs=outputs)
            result = handle.get_result()  # HTTP InferAsyncRequest / GRPC CallContext
        else:
            result = client.infer(args.model_name, [inp], outputs=outputs)
        print(f"Top {args.classes} classes:")
        postprocess(result, output_name, args.classes)
        print("PASS: image_client")


if __name__ == "__main__":
    main()
