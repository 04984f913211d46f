"""NLP tasks: Shakespeare char LSTM and the Reddit GRU word LM.

Parity targets:

- ``RNN`` (reference ``experiments/nlp_rnn_fedshakespeare/model.py:12-40``):
  embedding(90 -> 8, pad id 0) -> 2-layer LSTM(256) -> per-position dense to
  vocab; cross-entropy with ``ignore_index=0``; accuracy over non-pad
  positions.
- ``GRU`` (reference ``experiments/nlg_gru/model.py:11-133``): custom GRU
  cell (convex-combination update ``hy = n + i*(h - n)``), tied
  embedding/unembedding through a ``squeeze`` projection, negative ids mark
  padding, and OOV-rejecting accuracy: a prediction of the unk id (0) counts
  as wrong even when the target is 0 (``model.py:118-121``).

TPU-native: recurrences are ``nn.RNN``/``lax.scan`` (single compiled cell
per layer), embeddings gathered on-device, losses masked — no ragged
batches, no ``pack_padded_sequence``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..utils.metrics import Metric
from .base import BaseTask, Batch, parse_dtype, softmax_xent


class _ShakespeareLSTM(nn.Module):
    vocab_size: int = 90
    embed_dim: int = 8
    hidden: int = 256
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):  # x: [B, L] int32
        emb = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype)(x)
        h = emb
        for _ in range(2):
            h = nn.RNN(nn.OptimizedLSTMCell(self.hidden, dtype=self.dtype))(h)
        return nn.Dense(self.vocab_size, dtype=self.dtype)(h)  # [B, L, V]


class _ConvexGRUCell(nn.Module):
    """The reference's GRU2 cell (``nlg_gru/model.py:11-28``):
    ``hy = new + input_gate * (hidden - new)``."""

    hidden: int

    @nn.compact
    def __call__(self, carry, x):
        h = carry
        gi = nn.Dense(3 * self.hidden, use_bias=True, name="w_ih")(x)
        gh = nn.Dense(3 * self.hidden, use_bias=True, name="w_hh")(h)
        i_r, i_i, i_n = jnp.split(gi, 3, axis=-1)
        h_r, h_i, h_n = jnp.split(gh, 3, axis=-1)
        reset = jax.nn.sigmoid(i_r + h_r)
        inp = jax.nn.sigmoid(i_i + h_i)
        new = jnp.tanh(i_n + reset * h_n)
        hy = new + inp * (h - new)
        return hy, hy

    @staticmethod
    def init_carry(batch, hidden):
        return jnp.zeros((batch, hidden))


class _GRUWordLM(nn.Module):
    """Tied-embedding GRU LM (``nlg_gru/model.py:39-83``)."""

    vocab_size: int = 10000
    embed_dim: int = 160
    hidden_dim: int = 512

    @nn.compact
    def __call__(self, x):  # x: [B, L] int32 (already clamped non-negative)
        table = self.param(
            "embedding",
            lambda key, shape: jax.random.uniform(
                key, shape, minval=-(3 / shape[1]) ** 0.5,
                maxval=(3 / shape[1]) ** 0.5),
            (self.vocab_size, self.embed_dim))
        unembed_bias = self.param("unembedding_bias", nn.initializers.zeros,
                                  (self.vocab_size,))
        emb = jnp.take(table, x, axis=0)  # [B, L, E]

        carry = _ConvexGRUCell.init_carry(x.shape[0], self.hidden_dim)
        _, hiddens = nn.scan(
            _ConvexGRUCell, variable_broadcast="params",
            split_rngs={"params": False}, in_axes=1, out_axes=1,
        )(hidden=self.hidden_dim)(carry, emb)
        # the reference stacks [h0, h1, ..., hL] (``GRU2.forward``,
        # ``nlg_gru/model.py:31-36``): the zero INITIAL state's prediction
        # — the marginal next-word distribution — is part of the output
        # and of the loss (``model.py:92-100`` pairs output[:, t] with
        # input[:, t], including t=0 from h0)
        hiddens = jnp.concatenate(
            [jnp.zeros_like(hiddens[:, :1]), hiddens], axis=1)
        squeezed = nn.Dense(self.embed_dim, use_bias=False, name="squeeze")(hiddens)
        logits = squeezed @ table.T + unembed_bias
        return logits  # [B, L+1, V]


class SequenceLMTask(BaseTask):
    """Shared masked seq-to-seq LM task.

    ``batch['x']``: ``[B, L]`` int ids, 0 = padding.  If ``batch['y']`` is
    present it is the per-position target (fed_shakespeare ships explicit
    targets); otherwise targets are ``x`` shifted left by one.
    Per-sequence ``sample_mask`` gates whole padded sequences; position mask
    is ``target != 0`` (the reference's ``ignore_index=0`` / ``>= 0``
    masking).
    """

    #: x/y/tok_mask are 0-padded ``[n, L]`` rows: the round packer may crop
    #: their common all-pad tail (length bucketing).  tok_mask MUST be in
    #: the set — its nonzeros mark real positions even where x holds the
    #: unk id 0, so it both gets cropped in lockstep with x and keeps the
    #: bucket from under-counting unk tokens.
    seq_pad_keys = ("x", "y", "tok_mask")

    #: reference-GRU loss alignment (``nlg_gru/model.py:92-100``): the
    #: module emits one MORE position than its input (the initial zero
    #: state's prediction), the forward consumes ``x[:, :-1]``, and the
    #: targets are the FULL ``x`` — position 0 is predicted from h0.
    #: False = standard shift alignment (Shakespeare implicit / RingLM).
    ref_initial_prediction: bool = False

    def __init__(self, module: nn.Module, seq_len: int, name: str,
                 oov_reject: bool = False):
        self.module = module
        self.seq_len = seq_len
        self.name = name
        self.oov_reject = oov_reject

    def init_params(self, rng: jax.Array):
        dummy = jnp.zeros((1, self.seq_len - 1), jnp.int32)
        return self.module.init(rng, dummy)["params"]

    def _apply(self, params, inputs):
        """The module's logits."""
        return self.module.apply({"params": params}, inputs)

    def _inputs_targets(self, batch: Batch):
        """What the module is fed, what it is scored against and the
        weight of each scored position."""
        x = batch["x"].astype(jnp.int32)
        if "y" in batch and batch["y"].ndim == x.ndim:
            # explicit per-position targets: with ref_initial_prediction
            # the module emits len(inputs)+1 positions, so feed L-1
            # inputs to keep logits aligned with the [B, L] targets
            # (y[t] is predicted from the state after x[0..t-1], with
            # y[0] from the initial state)
            inputs = x[:, :-1] if self.ref_initial_prediction else x
            targets = batch["y"].astype(jnp.int32)
            tok_mask = batch.get("tok_mask")
            tok_mask = (tok_mask.astype(jnp.float32) if tok_mask is not None
                        else (targets != 0).astype(jnp.float32))
        elif self.ref_initial_prediction:
            # reference-GRU alignment: module([B, L-1]) -> [B, L, V]
            # (initial-state prediction included); targets = full x
            inputs, targets = x[:, :-1], x
            tok_mask = batch.get("tok_mask")
            tok_mask = (tok_mask.astype(jnp.float32) if tok_mask is not None
                        else (targets != 0).astype(jnp.float32))
        else:
            inputs, targets = x[:, :-1], x[:, 1:]
            tok_mask = batch.get("tok_mask")
            if tok_mask is not None:
                # mask for the shifted targets: a target is real iff its
                # position was real (keeps unk id 0 in the denominator, as
                # the reference's >=0 padding rule does)
                tok_mask = tok_mask.astype(jnp.float32)[:, 1:]
            else:
                tok_mask = (targets != 0).astype(jnp.float32)
        return inputs, targets, tok_mask * batch["sample_mask"][:, None]

    def _logits_targets(self, params, batch: Batch):
        inputs, targets, tok_mask = self._inputs_targets(batch)
        # f32 logits regardless of the module's compute dtype (bf16 MXU
        # matmuls, float32 softmax/xent — see models.base.parse_dtype)
        logits = self._apply(params, inputs).astype(jnp.float32)
        return logits, targets, tok_mask

    #: how the TRAINER counts this task's samples for aggregation weights
    #: and the DGA softmax metric (reference ``core/trainer.py:397-405``:
    #: rows by default, ``total_frames`` — real token positions — when the
    #: batch ships them, as nlg_gru's does).  fed_shakespeare batches ship
    #: neither key, so the LSTM task keeps row counting.
    count_frames = False

    def loss(self, params, batch: Batch, rng: Optional[jax.Array] = None,
             train: bool = True):
        return self._masked_xent(*self._logits_targets(params, batch), batch)

    def _masked_xent(self, logits, targets, tok_mask, batch: Batch):
        per_tok = softmax_xent(logits, targets)
        total = jnp.sum(per_tok * tok_mask)
        count = jnp.maximum(jnp.sum(tok_mask), 1.0)
        aux = {"sample_count": jnp.sum(batch["sample_mask"])}
        if self.count_frames:
            # reference total_frames = sum of real INPUT positions
            # (``experiments/nlg_gru/dataloaders/dataloader.py:83``); the
            # input-position mask counts them regardless of unk ids
            inp = batch.get("tok_mask")
            frames = (jnp.sum(inp.astype(jnp.float32)
                              * batch["sample_mask"][:, None])
                      if inp is not None else
                      jnp.sum((batch["x"] != 0).astype(jnp.float32)
                              * batch["sample_mask"][:, None]))
            aux["train_sample_count"] = frames
        return total / count, aux

    def topk_predictions(self, params, batch: Batch, k: int = 1):
        """Top-K predictions per target position (the reference GRU's
        ``wantLogits`` output payload, ``nlg_gru/model.py:113-130``):
        returns ``(probabilities, predictions, labels)`` with shapes
        ``[..., k]`` / ``[..., k]`` / ``[...]``; padded positions carry
        label -1."""
        logits, targets, tok_mask = self._logits_targets(params, batch)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_ids = jax.lax.top_k(probs, k)
        labels = jnp.where(tok_mask > 0, targets, -1)
        return top_p, top_ids, labels

    def token_logprobs(self, params, batch: Batch):
        """Per-token log-prob of the target under the model + validity mask
        (the ``compute_perplexity`` hook for the leakage attack, reference
        ``extensions/privacy/metrics.py:25-30``)."""
        logits, targets, tok_mask = self._logits_targets(params, batch)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return picked, tok_mask

    def eval_stats(self, params, batch: Batch) -> Dict[str, jnp.ndarray]:
        logits, targets, tok_mask = self._logits_targets(params, batch)
        per_tok = softmax_xent(logits, targets)
        pred = jnp.argmax(logits, axis=-1)
        correct = (pred == targets).astype(jnp.float32)
        if self.oov_reject:
            # predictions of the unk id count as wrong (nlg_gru model.py:118-121)
            correct = correct * (pred != 0)
        return {
            "loss_sum": jnp.sum(per_tok * tok_mask),
            "correct_sum": jnp.sum(correct * tok_mask),
            "sample_count": jnp.sum(tok_mask),
            "seq_count": jnp.sum(batch["sample_mask"]),
        }


class _TokenDatasetMixin:
    """make_dataset for token-sequence tasks: raw strings are tokenized
    (chars for Shakespeare, vocab words for the GRU LM), int sequences pass
    through 0-padded to ``seq_len``."""

    tokenizer: str = "words"  # or "chars"

    def row_fields(self, entry, split: str, user: int) -> dict:
        """Further per-position arrays ``[rows, L]`` of one user's rows
        (``entry``: its ``x`` and ``tok_mask``), carried to the loss
        beside them; a task that has some also lists them in
        ``seq_pad_keys``."""
        return {}

    def make_dataset(self, blob, model_config, split, data_config=None):
        import numpy as np
        from ..data.dataset import ArraysDataset
        from ..data import featurize

        vocab = None
        vocab_path = (model_config.get("vocab_dict") or
                      (data_config.get("vocab_dict") if data_config else None))
        if self.tokenizer == "words" and vocab_path:
            vocab = featurize.load_vocab(vocab_path)
        L = self.seq_len

        def encode_rows(samples):
            rows = []
            for s in samples:
                if isinstance(s, str):
                    if self.tokenizer == "chars":
                        rows.append(featurize.encode_chars(s, L))
                    else:
                        if vocab is None:
                            raise ValueError(
                                "word task needs vocab_dict for raw text")
                        rows.append(featurize.encode_words(s, vocab, L))
                elif isinstance(s, (list, tuple)) and s and \
                        isinstance(s[0], str):
                    if vocab is None:
                        raise ValueError(
                            "word task needs vocab_dict for raw tokens")
                    rows.append(featurize.encode_words(s, vocab, L))
                else:
                    rows.append(np.asarray(s))
            return featurize.pad_token_matrix(rows, L)

        per_user = []
        for i in range(len(blob)):
            x, tok_mask = encode_rows(blob.user_data[i])
            entry = {"x": x, "tok_mask": tok_mask}
            if blob.user_labels is not None and \
                    blob.user_labels[i] is not None:
                # fed_shakespeare-style explicit target sequences
                y, y_mask = encode_rows(blob.user_labels[i])
                entry["y"] = y
                entry["tok_mask"] = y_mask
            entry.update(self.row_fields(entry, split, i))
            per_user.append(entry)
        return ArraysDataset(blob.user_list, per_user,
                             [len(u["x"]) for u in per_user])


class ShakespeareTask(_TokenDatasetMixin, SequenceLMTask):
    tokenizer = "chars"


class GRUWordTask(_TokenDatasetMixin, SequenceLMTask):
    tokenizer = "words"
    # the reference GRU trains position 0 from the zero initial state
    ref_initial_prediction = True
    # nlg_gru batches carry total_frames: the trainer counts WORDS, not
    # utterances (invisible under equal-sized users — the normalized
    # aggregate cancels a constant factor — but load-bearing for FedAvg
    # weights on unequal users and for DGA's train_loss/num_samples)
    count_frames = True


def make_shakespeare_lstm_task(model_config) -> SequenceLMTask:
    vocab = int(model_config.get("vocab_size", 90))
    module = _ShakespeareLSTM(
        vocab_size=vocab,
        embed_dim=int(model_config.get("embed_dim", 8)),
        hidden=int(model_config.get("hidden_dim", 256)),
        dtype=parse_dtype(model_config))
    return ShakespeareTask(module,
                           seq_len=int(model_config.get("seq_len", 80)),
                           name="nlp_rnn_fedshakespeare")


def make_gru_lm_task(model_config) -> SequenceLMTask:
    module = _GRUWordLM(
        vocab_size=int(model_config.get("vocab_size", 10000)),
        embed_dim=int(model_config.get("embed_dim", 160)),
        hidden_dim=int(model_config.get("hidden_dim", 512)))
    return GRUWordTask(module,
                       seq_len=int(model_config.get("max_num_words", 25)),
                       name="nlg_gru", oov_reject=True)
