"""Block-diffusion training of a small decoder through the Estimator
(BD3-LM, arXiv:2503.09573: the training side of block-diffusion language
models such as SDAR).

Each step corrupts every block of ``BLOCK`` positions at a rate of its
own, runs the decoder ONCE over the noisy copy followed by the clean copy
of each sequence under one three-region attention mask
(``ops.flash_attention.BlockDiffusionMask``), and weighs the masked
positions' cross-entropy by ``1 / t``. The data are arithmetic sequences
modulo the vocabulary, so a masked id follows from the clean text before
its block, which is exactly what a noisy block is allowed to see.
"""

import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import numpy as np

VOCAB, SEQ, BLOCK = 32, 32, 4
MASK_ID = VOCAB - 1


def make_sequences(n, seed=0):
    """``x[i] = (start + step * i) mod (VOCAB - 1)``: ids below the mask
    id; a sequence is known from any two of its neighbours."""
    rng = np.random.default_rng(seed)
    start = rng.integers(0, VOCAB - 1, (n, 1))
    step = rng.integers(1, 4, (n, 1))
    return ((start + step * np.arange(SEQ)) % (VOCAB - 1)).astype(np.int32)


def main():
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.text.block_diffusion import BlockDiffusionLM
    from analytics_zoo_tpu.text.hybrid_decoder import HybridDecoderConfig

    zoo.init_orca_context(cluster_mode="local")
    decoder = HybridDecoderConfig(
        vocab=VOCAB, hidden_size=32, layer_types=("full_attention",) * 2,
        num_heads=4, num_kv_heads=2, intermediate_size=64,
        moe_intermediate_size=32, num_dense_layers=0, num_experts=4,
        num_experts_per_tok=2, rope_theta=1e4, router_scoring="softmax",
        tie_embeddings=False)
    module = BlockDiffusionLM(decoder, block=BLOCK, mask_id=MASK_ID)
    x = make_sequences(512)
    est = Estimator.from_flax(
        model=module,
        loss="weighted_sparse_categorical_crossentropy_logits",
        optimizer={"name": "adam", "learningrate": 3e-3},
        sample_input=x[:2])
    # the labels are the ids themselves: every masked position of the
    # noisy copy is trained for its own clean id
    hist = est.fit((x, x), epochs=12, batch_size=64)
    first, last = hist["loss"][0], hist["loss"][-1]
    print(f"weighted loss {first:.3f} -> {last:.3f}")
    assert last < 0.6 * first, (first, last)

    # outside training the module returns one array: the clean text's
    # logits under the mask's clean part
    logits = np.asarray(est.predict(x[:8], batch_size=8))
    assert logits.shape == (8, SEQ, VOCAB)
    print("predict:", logits.shape)
    zoo.stop_orca_context()


if __name__ == "__main__":
    main()
