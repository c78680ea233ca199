"""Sample: train a tiny character LM, export a package, and decode
with the NATIVE C++ runtime — no Python in the serving loop.

Demonstrates the dependency-free CPU serving path (the libVeles role,
SURVEY.md §2.10, upgraded to transformers): the exported package
(contents.json + .npy) loads through ``services.native.NativeWorkflow``
and generates with per-block KV caches, token-exact vs the Python
greedy decoder.

    python samples/native_serve.py            # standalone script
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    # serving here is deliberately CPU (the point of the native
    # runtime): pin it before jax is imported
    os.environ["JAX_PLATFORMS"] = "cpu"

    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.models.zoo import transformer_lm
    from veles_tpu.services.export import export_workflow
    from veles_tpu.services.native import NativeWorkflow

    prng.seed_all(11)
    text = b"the quick brown fox jumps over the lazy dog. " * 48
    seq = 32
    n = len(text) // seq
    tokens = np.frombuffer(text[:n * seq], np.uint8) \
        .reshape(n, seq).astype(np.int32)
    loader = FullBatchLoader(None, data=tokens, labels=tokens,
                             minibatch_size=16,
                             class_lengths=[0, 0, n])
    wf = StandardWorkflow(
        layers=transformer_lm(vocab_size=256, d_model=64, n_heads=4,
                              n_layers=2, dropout=0.0, pos="rope",
                              lr=3e-3),
        loader=loader, loss="lm",
        decision_config={"max_epochs": 20}, name="native-serve-demo")
    wf.initialize()
    wf.run()

    path = os.path.join(tempfile.mkdtemp(), "char_lm.zip")
    export_workflow(wf, path)
    print("exported:", path)

    native = NativeWorkflow(path)
    prompt = np.frombuffer(b"the quick brown ", np.uint8) \
        .astype(np.int32)
    toks = native.generate(prompt, max_new=16)
    print("C++ greedy :", bytes(toks.astype(np.uint8)).decode(
        "latin-1"))
    toks = native.generate(prompt, max_new=16, temperature=0.8,
                           top_k=8, seed=3)
    print("C++ sampled:", bytes(toks.astype(np.uint8)).decode(
        "latin-1"))
    native.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
