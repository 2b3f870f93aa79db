"""Tests for Transformer/BERT modules, keras layers and task estimators
(mirrors ref pyzoo/test/zoo/tfpark/test_text_estimators.py +
layers/TransformerLayerSpec.scala / BERTSpec.scala)."""

import numpy as np
import pytest

from analytics_zoo_tpu.text import (
    BERTClassifier, BERTNER, BERTSQuAD, BertConfig, BertModule,
    TransformerModule,
)

CFG = BertConfig(vocab=50, hidden_size=16, n_block=2, n_head=2,
                 intermediate_size=32, max_position_len=32,
                 hidden_drop=0.0, attn_drop=0.0)


def _toy_batch(b=8, L=12, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(1, 50, (b, L)).astype(np.int32)
    seg = np.zeros((b, L), np.int32)
    mask = np.ones((b, L), np.int32)
    mask[:, L - 3:] = 0  # padded tail
    return ids, seg, mask


class TestModules:
    def test_bert_shapes(self):
        import jax
        ids, seg, mask = _toy_batch()
        m = BertModule(CFG)
        variables = m.init(jax.random.PRNGKey(0), ids, seg, mask)
        seq, pooled = m.apply(variables, ids, seg, mask)
        assert seq.shape == (8, 12, 16)
        assert pooled.shape == (8, 16)

    def test_padding_mask_blocks_attention(self):
        """Changing a masked-out token must not change unmasked positions'
        representations (ref BERT attention-mask semantics)."""
        import jax
        ids, seg, mask = _toy_batch()
        m = BertModule(CFG)
        variables = m.init(jax.random.PRNGKey(0), ids, seg, mask)
        seq1, _ = m.apply(variables, ids, seg, mask)
        ids2 = ids.copy()
        ids2[:, -1] = (ids2[:, -1] % 49) + 1  # mutate a masked position
        seq2, _ = m.apply(variables, ids2, seg, mask)
        np.testing.assert_allclose(np.asarray(seq1[:, :9]),
                                   np.asarray(seq2[:, :9]), atol=1e-5)

    def test_transformer_causality(self):
        """Causal stack: mutating a future token must not change past
        positions (ref TransformerLayer causal masking)."""
        import jax
        rng = np.random.RandomState(1)
        ids = rng.randint(1, 50, (4, 10)).astype(np.int32)
        m = TransformerModule(vocab=50, hidden_size=16, n_block=2, n_head=2,
                              hidden_drop=0.0, max_position_len=16)
        variables = m.init(jax.random.PRNGKey(0), ids)
        out1 = m.apply(variables, ids)
        ids2 = ids.copy()
        ids2[:, -1] = (ids2[:, -1] % 49) + 1
        out2 = m.apply(variables, ids2)
        np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                                   np.asarray(out2[:, :-1]), atol=1e-5)
        assert np.abs(np.asarray(out1[:, -1]) -
                      np.asarray(out2[:, -1])).max() > 1e-4


class TestKerasLayers:
    def test_bert_layer_in_model(self, orca_ctx):
        from analytics_zoo_tpu.keras.engine import Input
        from analytics_zoo_tpu.keras.layers import BERT, Dense
        from analytics_zoo_tpu.keras.models import Model

        inp = Input(shape=(12,))
        pooled = BERT(vocab=50, hidden_size=16, n_block=1, n_head=2,
                      intermediate_size=32, max_position_len=32,
                      hidden_drop=0.0, attn_drop=0.0)(inp)
        out = Dense(3, activation="softmax")(pooled)
        m = Model(inp, out)
        ids = np.random.RandomState(0).randint(1, 50, (4, 12)).astype(
            np.float32)
        probs = np.asarray(m.predict(ids, distributed=False))
        assert probs.shape == (4, 3)
        np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-4)

    def test_transformer_layer_shape(self, orca_ctx):
        from analytics_zoo_tpu.keras.engine import Input
        from analytics_zoo_tpu.keras.layers import TransformerLayer
        from analytics_zoo_tpu.keras.models import Model

        inp = Input(shape=(10,))
        seq = TransformerLayer(vocab=50, hidden_size=16, n_block=1,
                               n_head=2, seq_len=16, hidden_drop=0.0)(inp)
        m = Model(inp, seq)
        ids = np.random.RandomState(0).randint(1, 50, (4, 10)).astype(
            np.float32)
        assert np.asarray(m.predict(ids, distributed=False)).shape \
            == (4, 10, 16)


class TestEstimators:
    def test_classifier_learns(self, orca_ctx):
        ids, seg, mask = _toy_batch(b=64, L=12)
        # learnable signal: class = whether token 7 appears early
        labels = (ids[:, :4] == 7).any(1).astype(np.int32)
        est = BERTClassifier(num_classes=2, config=CFG, seq_len=12)
        h1 = est.fit(ids, labels, token_type_ids=seg, input_mask=mask,
                     epochs=1, batch_size=16)
        h2 = est.fit(ids, labels, token_type_ids=seg, input_mask=mask,
                     epochs=8, batch_size=16)
        assert h2["loss"][-1] < h1["loss"][0]
        probs = np.asarray(est.predict(ids, seg, mask, batch_size=16))
        assert probs.shape == (64, 2)

    def test_sequence_longer_than_positions_raises(self):
        import jax
        ids = np.zeros((2, 40), np.int32)
        m = BertModule(CFG)  # max_position_len=32
        with pytest.raises(ValueError, match="max_position_len"):
            m.init(jax.random.PRNGKey(0), ids)

    def test_ner_loss_ignores_padding(self):
        """Mutating labels at masked positions must not change the loss."""
        from analytics_zoo_tpu.text.estimators import _ner_loss
        rng = np.random.RandomState(0)
        logits = rng.randn(4, 8, 3).astype(np.float32)
        labels = rng.randint(0, 3, (4, 8))
        labels_masked = labels.copy()
        labels_masked[:, 6:] = -1
        l1 = np.asarray(_ner_loss(labels_masked, logits))
        garbage = labels.copy()
        garbage[:, 6:] = -7  # different negative marker, same mask
        l2 = np.asarray(_ner_loss(garbage, logits))
        np.testing.assert_allclose(l1, l2)
        # and differs from the unmasked loss
        l3 = np.asarray(_ner_loss(labels, logits))
        assert np.abs(l1 - l3).max() > 1e-6

    def test_ner_shapes_and_training(self, orca_ctx):
        ids, seg, mask = _toy_batch(b=32, L=12)
        tags = (ids % 3).astype(np.int32)  # learnable per-token tags
        est = BERTNER(num_entities=3, config=CFG, seq_len=12)
        h = est.fit(ids, tags, input_mask=mask, epochs=6, batch_size=16)
        assert h["loss"][-1] < h["loss"][0]
        out = np.asarray(est.predict(ids, seg, mask, batch_size=16))
        assert out.shape == (32, 12, 3)

    def test_squad_start_end(self, orca_ctx):
        ids, seg, mask = _toy_batch(b=32, L=12)
        labels = np.stack([np.full(32, 2), np.full(32, 5)], 1).astype(
            np.int32)
        est = BERTSQuAD(config=CFG, seq_len=12)
        h = est.fit(ids, labels, epochs=6, batch_size=16)
        assert h["loss"][-1] < h["loss"][0]
        start, end = est.predict(ids, seg, mask, batch_size=16)
        assert np.asarray(start).shape == (32, 12)
        assert np.asarray(end).shape == (32, 12)

    def test_save_load_roundtrip(self, orca_ctx, tmp_path):
        ids, seg, mask = _toy_batch(b=16, L=12)
        est = BERTClassifier(num_classes=2, config=CFG, seq_len=12)
        est.fit(ids, (ids[:, 0] % 2).astype(np.int32), epochs=1,
                batch_size=8)
        p1 = np.asarray(est.predict(ids, seg, mask, batch_size=8))
        path = str(tmp_path / "bert")
        est.save(path)
        est2 = BERTClassifier(num_classes=2, config=CFG, seq_len=12)
        est2.load(path)
        p2 = np.asarray(est2.predict(ids, seg, mask, batch_size=8))
        np.testing.assert_allclose(p2, p1, atol=1e-5)

    def test_tensor_parallel_bert(self, orca_ctx):
        """BERT under dp2,tp2 on the virtual 8-dev mesh: params really
        shard over the model axis (new capability vs reference)."""
        ids, seg, mask = _toy_batch(b=16, L=12)
        labels = (ids[:, 0] % 2).astype(np.int32)
        est = BERTClassifier(num_classes=2, config=CFG, seq_len=12,
                             strategy="dp,tp2")
        h = est.fit(ids, labels, epochs=1, batch_size=16)
        assert np.isfinite(h["loss"][0])
        state = est.estimator._state
        qk = state["params"]["bert"]["block_0"]["attention"]["query"]["kernel"]
        assert "model" in str(qk.sharding.spec), qk.sharding.spec


def test_remat_forward_and_grad_equivalence(orca_ctx):
    """BertConfig(remat=True) recomputes activations in backward without
    changing forward outputs or gradients."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.text.bert import BertConfig, BertModule

    kw = dict(vocab=100, hidden_size=32, n_block=2, n_head=2,
              intermediate_size=64, max_position_len=16,
              hidden_drop=0.0, attn_drop=0.0)
    ids = np.random.RandomState(0).randint(0, 100, (2, 16)).astype(np.int32)
    plain = BertModule(BertConfig(**kw))
    remat = BertModule(BertConfig(**kw, remat=True))
    variables = plain.init({"params": jax.random.PRNGKey(0),
                            "dropout": jax.random.PRNGKey(1)}, ids)
    np.testing.assert_allclose(
        np.asarray(plain.apply(variables, ids)[1]),
        np.asarray(remat.apply(variables, ids)[1]), atol=1e-6)

    def loss(module):
        return lambda v: jnp.sum(module.apply(v, ids)[1] ** 2)

    g1 = jax.grad(loss(plain))(variables)
    g2 = jax.grad(loss(remat))(variables)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
