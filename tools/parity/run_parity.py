"""Cross-framework parity harness: the ACTUAL reference (torch, mounted
read-only at /root/reference) vs msrflute_tpu on identical synthetic user
blobs, identical initial weights, matched hyperparameters.

Round-by-round val loss/acc trajectories are compared per task and written
to PARITY.json.  This is the strongest accuracy-parity evidence obtainable
with zero egress (real datasets unfetchable): both frameworks run their own
full federated stacks — reference thread-mode single process
(``core/federated.py:634-676``), msrflute_tpu its jitted SPMD round — and
must produce the same numbers.

Design notes:
- The reference runs from a symlink scratch tree (its plugin loaders
  resolve ``experiments/<task>`` against cwd; /root/reference is read-only
  so adapters are injected via the tree, never written there).
- Adapter tasks (tools/parity/adapters/) re-export the reference's own
  model/dataloader classes, adding only json-path loading.
- Identical init: one numpy weight set is written as a torch state_dict
  for the reference (``model_config.pretrained_model_path``,
  ``utils/utils.py:486-494``) and as a params-pytree msgpack for
  msrflute_tpu (same config key).  Layout conversions: torch Linear
  [out,in] -> flax kernel [in,out]; torch Conv [out,in,kh,kw] -> flax
  [kh,kw,in,out]; the CNN's flatten bridge permutes CHW->HWC flat order.
- Determinism: full participation (K == pool), one local epoch, one batch
  per client (batch_size >= samples/user), plain SGD both sides -> the
  trajectory is RNG-free except CNN dropout (LR is compared strictly;
  CNN by round-0 exactness + both-learned + matched endpoints, since
  dropout RNG time-offsets make pointwise mid-trajectory bands
  meaningless during steep descent).
- Images are stored pre-transposed for the reference (its __getitem__
  applies ``.T``, ``experiments/cv_lr_mnist/dataloaders/dataset.py:34``)
  and un-transposed for msrflute_tpu, so both models see the same tensors.

Usage: python tools/parity/run_parity.py [--tasks lr,cnn] [--rounds 20]

Extension modes (review round 3 item 2) ride the deterministic LR base and are
selected through the same --tasks flag: ``dga`` (softmax weighting),
``dga_quant`` (+8-bit gradient quantization), ``dp_clip`` (clip-only local
DP, eps<0), ``dp_tiny_noise`` (the full eps>0 dance at vanishing sigma +
global DP at sigma=0 — near-deterministic, so semantic divergence shows as
drift), ``dp_envelope`` (real noise, statistical-envelope criteria).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REFERENCE = "/root/reference"
ADAPTERS = os.path.join(REPO, "tools", "parity", "adapters")

#: sequential reference launches need distinct rendezvous ports (TIME_WAIT)
_REF_RUN_SEQ = 0


# ----------------------------------------------------------------------
# synthetic blobs
# ----------------------------------------------------------------------
def gen_blob(rng, users, samples, shape, classes, sep=2.0, means=None):
    """Class-structured gaussian data: learnable but not trivial.

    Pass the same ``means`` for train and val: a fresh draw per split
    would make validation distributionally unrelated to training and pin
    val accuracy at chance regardless of learning.  ``samples`` may be a
    per-user sequence — UNEVEN sizes make the sample-count aggregation
    weights load-bearing (equal users cancel any constant factor in the
    normalized aggregate)."""
    if means is None:
        means = rng.normal(size=(classes,) + shape).astype(np.float32)
    per_user = (list(samples) if isinstance(samples, (list, tuple))
                else [samples] * users)
    out = {"users": [], "num_samples": [], "user_data": {},
           "user_data_label": {}}
    for u in range(users):
        samples = per_user[u]
        y = rng.integers(0, classes, size=(samples,))
        x = (sep * means[y]
             + rng.normal(size=(samples,) + shape)).astype(np.float32)
        name = f"{u:04d}"
        out["users"].append(name)
        out["num_samples"].append(samples)
        out["user_data"][name] = {"x": x}
        out["user_data_label"][name] = y.astype(np.int64)
    return out


def write_blob(blob, path, transpose_images=False):
    def conv(x):
        x = np.asarray(x)
        if transpose_images and x.ndim == 3:  # [N, H, W] -> stored .T'd
            x = np.swapaxes(x, 1, 2)
        return x.tolist()

    js = {
        "users": blob["users"],
        "num_samples": blob["num_samples"],
        "user_data": {u: {"x": conv(d["x"])}
                      for u, d in blob["user_data"].items()},
        "user_data_label": {u: np.asarray(l).tolist()
                            for u, l in blob["user_data_label"].items()},
    }
    with open(path, "w") as fh:
        json.dump(js, fh)


def _markov_stream(rng, length, vocab, trans, noise):
    """One noisy-Markov token stream (ids 1..vocab-1): next id is
    ``trans[cur]`` with prob 1-noise, else uniform — the shared
    synthetic-language kernel of the lstm and gru blobs."""
    stream = np.empty(length, np.int64)
    stream[0] = rng.integers(1, vocab)
    for t in range(length - 1):
        stream[t + 1] = (rng.integers(1, vocab)
                         if rng.random() < noise
                         else trans[stream[t] - 1])
    return stream


def gen_lstm_blob(rng, users, samples, seq_len, vocab=90, trans=None,
                  noise=0.15):
    """Char sequences from a noisy deterministic next-char rule: with
    prob ``1-noise`` the next char is ``trans[cur]`` (a fixed random
    permutation of 1..vocab-1), else uniform — learnable structure for a
    next-char LSTM, never emitting the pad id 0 (so every target position
    is real and token- vs sequence-weighted metric aggregation coincide
    exactly across the two frameworks).  ``x`` is the stream's first L
    chars, ``y`` the next-char targets (the fed_shakespeare explicit-
    target blob shape).  Pass the same ``trans`` for train and val."""
    if trans is None:
        trans = rng.permutation(np.arange(1, vocab))
    out = {"users": [], "num_samples": [], "user_data": {},
           "user_data_label": {}}
    for u in range(users):
        xs, ys = [], []
        for _ in range(samples):
            stream = _markov_stream(rng, seq_len + 1, vocab, trans, noise)
            xs.append(stream[:seq_len])
            ys.append(stream[1:])
        name = f"{u:04d}"
        out["users"].append(name)
        out["num_samples"].append(samples)
        out["user_data"][name] = {"x": np.stack(xs)}
        out["user_data_label"][name] = np.stack(ys)
    return out


def gen_gru_blob(rng, users, seq_len, vocab=60, trans=None, noise=0.15):
    """nlg_gru-shaped blob: ONE word-id utterance per user (the
    reference's DynamicBatchSampler shuffles multi-utterance users with
    a wallclock-seeded epoch, so only 1 utt/user is order-deterministic;
    its frames budget == max_num_words then yields exactly one batch).
    Utterances are WORD STRINGS ("w<id>", all in-vocab) — the reference
    DatasetConfig has no ``preencoded`` field, so both frameworks
    tokenize through the same vocab file (case-backoff is a no-op for
    in-vocab words).  Ids stay in 1..vocab-1 (0 is the unk id the
    OOV-rejecting accuracy penalizes; never emitting it keeps both
    accuracy definitions trivially aligned), full length (no padding
    anywhere)."""
    if trans is None:
        trans = rng.permutation(np.arange(1, vocab))
    out = {"users": [], "num_samples": [], "user_data": {}}
    for u in range(users):
        stream = _markov_stream(rng, seq_len, vocab, trans, noise)
        name = f"{u:04d}"
        out["users"].append(name)
        out["num_samples"].append(1)
        out["user_data"][name] = {"x": [[f"w{i}" for i in stream]]}
    return out


def gen_bert_blob(rng, users, samples, seq_len, vocab, n_masked=3,
                  perm=None, n_special=5, mask_id=4):
    """MLM blob with PRECOMPUTED deterministic masking (review round 3 item 4:
    "precomputed mask tensors fed as data to sidestep collator RNG").

    Token rule: even positions draw a random id in [n_special, vocab); each
    odd position is a fixed permutation of its left neighbor — masked
    tokens are recoverable from context, so MLM training has real signal.
    Masking: EXACTLY ``n_masked`` positions per sequence (a fixed count
    makes the reference's batch-size-weighted val loss coincide with the
    token-weighted mean our sum-form eval computes), HF 80/10/10 rule
    applied here with numpy RNG; ``x`` ships already masked, labels carry
    the original ids at masked slots and -100 elsewhere.  Pass the same
    ``perm`` for train and val."""
    content = vocab - n_special
    if perm is None:
        perm = rng.permutation(content)
    out = {"users": [], "num_samples": [], "user_data": {},
           "user_data_label": {}}
    for u in range(users):
        xs, ys = [], []
        for _ in range(samples):
            seq = np.empty(seq_len, np.int64)
            for t in range(seq_len):
                if t % 2 == 0:
                    seq[t] = n_special + rng.integers(content)
                else:
                    seq[t] = n_special + perm[seq[t - 1] - n_special]
            labels = np.full(seq_len, -100, np.int64)
            masked = seq.copy()
            pos = rng.choice(seq_len, size=n_masked, replace=False)
            for p in pos:
                labels[p] = seq[p]
                roll = rng.random()
                if roll < 0.8:
                    masked[p] = mask_id
                elif roll < 0.9:
                    masked[p] = n_special + rng.integers(content)
                # else: keep original (the 10% "unchanged" arm)
            xs.append(masked)
            ys.append(labels)
        name = f"{u:04d}"
        out["users"].append(name)
        out["num_samples"].append(samples)
        out["user_data"][name] = {"x": np.stack(xs)}
        out["user_data_label"][name] = np.stack(ys)
    return out


def make_bert_checkpoint(work, vocab, hidden=32, layers=2, heads=2,
                         intermediate=64, seed=0):
    """Build ONE local tiny-BERT checkpoint dir both frameworks load: the
    reference via ``model_name_or_path`` -> ``AutoModelForMaskedLM
    .from_pretrained`` (``experiments/mlm_bert/model.py:119-123`` — this
    exercises its pretrained path end to end), ours via the same config
    key -> ``FlaxBertForMaskedLM.from_pretrained(..., from_pt=True)``.
    Loading one torch-saved dir on both sides IS the identical-init
    transplant (HF owns the layout conversion).  Dropout is zeroed in the
    saved config so both forwards are deterministic.  The vocab.txt rows
    count must equal vocab_size: the reference resizes embeddings to
    ``len(tokenizer)`` (``model.py:137``), which must be a no-op."""
    import torch
    from transformers import BertConfig, BertForMaskedLM, BertTokenizer
    cfg = BertConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, intermediate_size=intermediate,
        max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    torch.manual_seed(seed)
    model = BertForMaskedLM(cfg)
    ckpt = os.path.join(work, "bert_ckpt")
    os.makedirs(ckpt, exist_ok=True)
    model.save_pretrained(ckpt)
    vocab_file = os.path.join(ckpt, "vocab.txt")
    with open(vocab_file, "w") as fh:
        for w in (["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
                  + [f"tok{i}" for i in range(vocab - 5)]):
            fh.write(w + "\n")
    BertTokenizer(vocab_file).save_pretrained(ckpt)
    return ckpt


def write_gru_blob(blob, path):
    with open(path, "w") as fh:
        json.dump(blob, fh)


def write_vocab(path, vocab):
    """Plain-txt vocab (one word per line): line index i maps word
    "w<i>" to id i in BOTH frameworks' loaders (nlg_gru utils
    ``load_vocab`` and ``msrflute_tpu.data.featurize.load_vocab``) —
    the vocab is load-bearing, since both sides tokenize the string
    blobs through it."""
    with open(path, "w") as fh:
        for i in range(vocab):
            fh.write(f"w{i}\n")


# ----------------------------------------------------------------------
# identical initial weights
# ----------------------------------------------------------------------
def lr_init(rng, input_dim=784, classes=10):
    scale = 1.0 / np.sqrt(input_dim)
    return {
        "w": rng.uniform(-scale, scale,
                         size=(classes, input_dim)).astype(np.float32),
        "b": rng.uniform(-scale, scale, size=(classes,)).astype(np.float32),
    }


def cnn_init(rng, classes=62):
    def kaiming(shape, fan_in):
        # torch kaiming_uniform_(a=sqrt(5)) default: bound = sqrt(6/((1+5)fan_in))
        bound = np.sqrt(6.0 / (6.0 * fan_in))
        return rng.uniform(-bound, bound, size=shape).astype(np.float32)

    def bias(shape, fan_in):
        bound = 1.0 / np.sqrt(fan_in)
        return rng.uniform(-bound, bound, size=shape).astype(np.float32)

    return {
        "conv1_w": kaiming((32, 1, 3, 3), 9), "conv1_b": bias((32,), 9),
        "conv2_w": kaiming((64, 32, 3, 3), 288), "conv2_b": bias((64,), 288),
        "fc1_w": kaiming((128, 9216), 9216), "fc1_b": bias((128,), 9216),
        "fc2_w": kaiming((classes, 128), 128), "fc2_b": bias((classes,), 128),
    }


def lstm_init(rng, vocab=90, embed=8, hidden=256):
    """torch-default init for the fed_shakespeare RNN: Embedding N(0,1)
    with the padding row zeroed, every nn.LSTM weight/bias
    uniform(-1/sqrt(H), 1/sqrt(H)), Linear kaiming-uniform(a=sqrt(5))
    (== uniform(-1/sqrt(fan_in), 1/sqrt(fan_in))) + matching bias."""
    k = 1.0 / np.sqrt(hidden)

    def u(shape):
        return rng.uniform(-k, k, size=shape).astype(np.float32)

    emb = rng.normal(size=(vocab, embed)).astype(np.float32)
    emb[0] = 0.0  # nn.Embedding(padding_idx=0) zeroes the pad row
    init = {"emb": emb}
    for layer, in_dim in ((0, embed), (1, hidden)):
        init[f"w_ih_l{layer}"] = u((4 * hidden, in_dim))
        init[f"w_hh_l{layer}"] = u((4 * hidden, hidden))
        init[f"b_ih_l{layer}"] = u((4 * hidden,))
        init[f"b_hh_l{layer}"] = u((4 * hidden,))
    bound = 1.0 / np.sqrt(hidden)
    init["fc_w"] = rng.uniform(-bound, bound,
                               size=(vocab, hidden)).astype(np.float32)
    init["fc_b"] = rng.uniform(-bound, bound,
                               size=(vocab,)).astype(np.float32)
    return init


def gru_init(rng, vocab=60, embed=16, hidden=64):
    """torch-default init for the nlg_gru GRU: embedding table
    uniform(±sqrt(3/E)) (Embedding.__init__), unembedding bias zeros,
    both GRU2 Linears kaiming-uniform(a=sqrt(5)) == uniform(±1/sqrt(in))
    with matching bias bounds, squeeze Linear (no bias) ditto."""
    def lin(out_dim, in_dim):
        b = 1.0 / np.sqrt(in_dim)
        return (rng.uniform(-b, b, size=(out_dim, in_dim)).astype(np.float32),
                rng.uniform(-b, b, size=(out_dim,)).astype(np.float32))

    delta = np.sqrt(3.0 / embed)
    table = rng.uniform(-delta, delta,
                        size=(vocab, embed)).astype(np.float32)
    w_ih, b_ih = lin(3 * hidden, embed)
    w_hh, b_hh = lin(3 * hidden, hidden)
    sq_w, _ = lin(embed, hidden)
    return {"table": table,
            "unembedding_bias": np.zeros((vocab,), np.float32),
            "w_ih": w_ih, "b_ih": b_ih, "w_hh": w_hh, "b_hh": b_hh,
            "squeeze": sq_w}


def save_torch_gru(init, path):
    import torch
    # the GRU model's submodules hang directly off self (no .net wrapper,
    # unlike the LR/CNN/RNN task classes)
    sd = {"embedding.table": torch.tensor(init["table"]),
          "embedding.unembedding_bias": torch.tensor(
              init["unembedding_bias"]),
          "rnn.w_ih.weight": torch.tensor(init["w_ih"]),
          "rnn.w_ih.bias": torch.tensor(init["b_ih"]),
          "rnn.w_hh.weight": torch.tensor(init["w_hh"]),
          "rnn.w_hh.bias": torch.tensor(init["b_hh"]),
          "squeeze.weight": torch.tensor(init["squeeze"])}
    torch.save(sd, path)


def save_flax_gru(init, path):
    """GRU2 keeps the three gates (r, i, n) stacked in one [3H, in]
    Linear on each side — our _ConvexGRUCell mirrors that layout exactly
    (same order, jnp.split), so only the Linear [out,in] -> flax [in,out]
    transposes apply."""
    from flax import serialization
    params = {
        "embedding": init["table"],
        "unembedding_bias": init["unembedding_bias"],
        "Scan_ConvexGRUCell_0": {
            "w_ih": {"kernel": init["w_ih"].T, "bias": init["b_ih"]},
            "w_hh": {"kernel": init["w_hh"].T, "bias": init["b_hh"]},
        },
        "squeeze": {"kernel": init["squeeze"].T},
    }
    with open(path, "wb") as fh:
        fh.write(serialization.msgpack_serialize(
            serialization.to_state_dict(params)))


def save_torch_lr(init, path):
    import torch
    sd = {"net.linear.weight": torch.tensor(init["w"]),
          "net.linear.bias": torch.tensor(init["b"])}
    torch.save(sd, path)


def save_torch_cnn(init, path):
    import torch
    sd = {
        "net.conv2d_1.weight": torch.tensor(init["conv1_w"]),
        "net.conv2d_1.bias": torch.tensor(init["conv1_b"]),
        "net.conv2d_2.weight": torch.tensor(init["conv2_w"]),
        "net.conv2d_2.bias": torch.tensor(init["conv2_b"]),
        "net.linear_1.weight": torch.tensor(init["fc1_w"]),
        "net.linear_1.bias": torch.tensor(init["fc1_b"]),
        "net.linear_2.weight": torch.tensor(init["fc2_w"]),
        "net.linear_2.bias": torch.tensor(init["fc2_b"]),
    }
    torch.save(sd, path)


def save_torch_lstm(init, path):
    import torch
    sd = {"net.embeddings.weight": torch.tensor(init["emb"]),
          "net.fc.weight": torch.tensor(init["fc_w"]),
          "net.fc.bias": torch.tensor(init["fc_b"])}
    for layer in (0, 1):
        for name in ("w_ih", "w_hh", "b_ih", "b_hh"):
            sd[f"net.lstm.{name.replace('w_', 'weight_').replace('b_', 'bias_')}_l{layer}"] = \
                torch.tensor(init[f"{name}_l{layer}"])
    torch.save(sd, path)


def save_flax_lstm(init, path, hidden=256):
    """torch nn.LSTM -> flax OptimizedLSTMCell: torch stacks the four
    gates (i, f, g, o) along dim 0 of weight_ih/weight_hh ([4H, in]) with
    two bias vectors (bias_ih + bias_hh, always summed in the cell); flax
    names per-gate Dense blocks — input kernels ``i{g}`` [in, H] without
    bias, hidden kernels ``h{g}`` [H, H] carrying the single bias."""
    from flax import serialization
    H = hidden
    params = {"Embed_0": {"embedding": init["emb"]},
              "Dense_0": {"kernel": init["fc_w"].T, "bias": init["fc_b"]}}
    for layer in (0, 1):
        cell = {}
        for k, g in enumerate("ifgo"):
            sl = slice(k * H, (k + 1) * H)
            cell[f"i{g}"] = {"kernel": init[f"w_ih_l{layer}"][sl].T}
            cell[f"h{g}"] = {"kernel": init[f"w_hh_l{layer}"][sl].T,
                             "bias": (init[f"b_ih_l{layer}"][sl]
                                      + init[f"b_hh_l{layer}"][sl])}
        params[f"OptimizedLSTMCell_{layer}"] = cell
    with open(path, "wb") as fh:
        fh.write(serialization.msgpack_serialize(
            serialization.to_state_dict(params)))


def save_flax_lr(init, path):
    from flax import serialization
    params = {"Dense_0": {"kernel": init["w"].T, "bias": init["b"]}}
    with open(path, "wb") as fh:
        fh.write(serialization.msgpack_serialize(
            serialization.to_state_dict(params)))


def save_flax_cnn(init, path):
    from flax import serialization
    # conv: [out,in,kh,kw] -> [kh,kw,in,out]
    # fc1 bridge: torch flattens NCHW [64,12,12] C-major; flax flattens
    # NHWC [12,12,64] HW-major -> permute fc1's input axis accordingly
    fc1 = init["fc1_w"].reshape(128, 64, 12, 12).transpose(0, 2, 3, 1)
    fc1 = fc1.reshape(128, 9216)
    params = {
        "Conv_0": {"kernel": init["conv1_w"].transpose(2, 3, 1, 0),
                   "bias": init["conv1_b"]},
        "Conv_1": {"kernel": init["conv2_w"].transpose(2, 3, 1, 0),
                   "bias": init["conv2_b"]},
        "Dense_0": {"kernel": fc1.T, "bias": init["fc1_b"]},
        "Dense_1": {"kernel": init["fc2_w"].T, "bias": init["fc2_b"]},
    }
    with open(path, "wb") as fh:
        fh.write(serialization.msgpack_serialize(
            serialization.to_state_dict(params)))


# ----------------------------------------------------------------------
# configs
# ----------------------------------------------------------------------
GRU_DIMS = {"vocab_size": 60, "embed_dim": 16, "hidden_dim": 64}


BERT_DIMS = {"vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 2, "intermediate_size": 64}


def ref_config(task, rounds, users, batch, lr, init_path, outdim):
    model = {"model_type": {"lr": "LR", "cnn": "CNN", "lstm": "RNN",
                            "gru": "GRU", "bert": "BERT"}[task],
             "model_folder": f"experiments/parity_{task}/model.py"}
    if task == "bert":
        # init_path is the shared local checkpoint DIR (make_bert_checkpoint)
        # loaded through the reference's own pretrained path; no torch
        # state-dict transplant needed
        # schema (core/schema.py:24-31) REQUIRES model_name and
        # process_line_by_line; config validate (core/config.py:753-759)
        # propagates model_name (NOT model_name_or_path) into every data
        # config as the tokenizer path — so model_name must also be the
        # local checkpoint dir.  cache_dir/use_fast_tokenizer are read
        # unconditionally by the model/dataloaders.
        model["BERT"] = {
            "model": {"model_name": init_path,
                      "model_name_or_path": init_path,
                      "process_line_by_line": False,
                      # the model code's own default (True,
                      # model.py:69) crashes eval at preds.size();
                      # the experiment config class defaults False
                      # (experiments/mlm_bert/config.py:43)
                      "prediction_loss_only": False,
                      "cache_dir": None, "use_fast_tokenizer": False,
                      "mask_token_id": 4},
            "training": {"seed": 0, "label_smoothing_factor": 0,
                         "batch_size": batch},
        }
    else:
        model["pretrained_model_path"] = init_path
    if task == "lr":
        model.update({"input_dim": 784, "output_dim": outdim})
    elif task == "gru":
        model.update(GRU_DIMS)
    return {
        "model_config": model,
        "dp_config": {"enable_local_dp": False},
        "privacy_metrics_config": {"apply_metrics": False},
        "strategy": "FedAvg",
        "server_config": {
            "wantRL": False, "resume_from_checkpoint": False,
            "do_profiling": False,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "annealing_config": {"type": "step_lr", "step_interval": "epoch",
                                 "gamma": 1.0, "step_size": 1000},
            "val_freq": 1, "rec_freq": 100000,
            "initial_val": True, "initial_rec": False,
            "max_iteration": rounds,
            "num_clients_per_iteration": users,
            "data_config": {
                "val": {"batch_size": 4096, "val_data": "val.json"},
                "test": {"batch_size": 4096, "test_data": "val.json"},
            },
            "type": "model_optimization",
            "aggregate_median": "softmax",
            "initial_lr_client": lr, "lr_decay_factor": 1.0,
            "weight_train_loss": "train_loss",
            "best_model_criterion": "loss",
            "fall_back_to_best_model": False, "softmax_beta": 1.0,
        },
        "client_config": {
            "do_profiling": False, "ignore_subtask": False,
            "data_config": {
                "train": {"batch_size": batch,
                          "list_of_train_data": "train.json",
                          "desired_max_samples": 100000},
            },
            "optimizer_config": {"type": "sgd", "lr": lr},
            "type": "optimization",
        },
    }


def tpu_config(task, rounds, users, batch, lr, init_path, outdim):
    model = {"model_type": {"lr": "LR", "cnn": "CNN", "lstm": "LSTM",
                            "gru": "GRU", "bert": "BERT"}[task]}
    if task == "bert":
        # same local checkpoint dir as the reference: identical init via
        # HF's own torch->flax conversion (models/bert.py from_pt fallback)
        model["BERT"] = {"model": {"model_name_or_path": init_path,
                                   "max_seq_length": outdim,
                                   "mask_token_id": 4,
                                   "premasked": True},
                         "training": {"seed": 0,
                                      "label_smoothing_factor": 0}}
    else:
        model["pretrained_model_path"] = init_path
    if task == "lr":
        model.update({"input_dim": 784, "num_classes": outdim,
                      "sigmoid_output": True})  # the reference LR quirk
    elif task == "lstm":
        # outdim carries seq_len for the lstm task (vocab is the
        # reference's hardcoded 90/8/256 architecture)
        model.update({"vocab_size": 90, "embed_dim": 8, "hidden_dim": 256,
                      "seq_len": outdim})
    elif task == "gru":
        model.update(dict(GRU_DIMS, max_num_words=outdim))
    else:
        model.update({"num_classes": outdim})
    return {
        "model_config": model,
        "strategy": "FedAvg",
        "server_config": {
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "annealing_config": {"type": "step_lr", "step_interval": "epoch",
                                 "gamma": 1.0, "step_size": 1000},
            "val_freq": 1, "rec_freq": 100000,
            "initial_val": True, "initial_rec": False,
            "max_iteration": rounds,
            "num_clients_per_iteration": users,
            "data_config": {
                "val": {"batch_size": 4096, "val_data": "val.json"},
                "test": {"batch_size": 4096, "test_data": "val.json"},
            },
            "type": "model_optimization",
            "initial_lr_client": lr, "lr_decay_factor": 1.0,
            "best_model_criterion": "loss",
        },
        "client_config": {
            "data_config": {
                "train": {"batch_size": batch,
                          "list_of_train_data": "train.json"},
            },
            "optimizer_config": {"type": "sgd", "lr": lr},
            "type": "optimization",
        },
    }


# ----------------------------------------------------------------------
# runners
# ----------------------------------------------------------------------
def build_ref_tree(scratch):
    """Symlink tree so the reference runs with our adapter experiments
    without writing to the read-only mount."""
    tree = os.path.join(scratch, "refrun")
    shutil.rmtree(tree, ignore_errors=True)
    os.makedirs(os.path.join(tree, "experiments"))
    for name in ("utils", "extensions", "e2e_trainer.py"):
        os.symlink(os.path.join(REFERENCE, name), os.path.join(tree, name))
    # core is symlinked per FILE so client.py can carry a one-line
    # runtime repair: the personalization branch unpacks TWO values from
    # train_desired_samples (core/client.py:427) which returns THREE
    # (core/trainer.py:339) — the reference's personalization training
    # crashes out of the box (docs/reference_quirks.md).  Patched in the
    # scratch tree only; nothing is copied into this repo.
    os.makedirs(os.path.join(tree, "core"))
    for name in os.listdir(os.path.join(REFERENCE, "core")):
        src = os.path.join(REFERENCE, "core", name)
        dst = os.path.join(tree, "core", name)
        if name == "client.py":
            with open(src) as fh:
                text = fh.read()
            broken = ("            train_loss, num_samples = "
                      "local_trainer.train_desired_samples(")
            fixed = ("            train_loss, num_samples, _ = "
                     "local_trainer.train_desired_samples(")
            assert broken in text, "reference client.py drifted; re-check"
            with open(dst, "w") as fh:
                fh.write(text.replace(broken, fixed, 1))
        else:
            os.symlink(src, dst)
    for name in os.listdir(os.path.join(REFERENCE, "experiments")):
        os.symlink(os.path.join(REFERENCE, "experiments", name),
                   os.path.join(tree, "experiments", name))
    for task in sorted(os.listdir(ADAPTERS)):  # every parity_* adapter
        if os.path.isdir(os.path.join(ADAPTERS, task)):
            os.symlink(os.path.join(ADAPTERS, task),
                       os.path.join(tree, "experiments", task))
    # the personalization server import is hardcoded to experiments/cv
    # (core/server.py:593-595) and the reference's own class there has a
    # stale constructor signature that crashes — remap cv to the
    # signature-current pass-through shim (see cv_server_shim/server.py)
    cv_link = os.path.join(tree, "experiments", "cv")
    os.remove(cv_link)
    os.symlink(os.path.join(ADAPTERS, "cv_server_shim"), cv_link)
    return tree


def run_reference(tree, cfg_path, data_dir, out_dir, task, metrics_out):
    """Run the reference in its REAL 2-process mode (server rank0 + worker
    rank1, gloo): the distributed path implements the documented FedAvg
    math.  (Thread mode, ``core/federated.py:683-707``, is avoided on
    purpose: on CPU ``tensor.to('cpu')`` is a no-copy alias, so its
    aggregate double-counts and the server steps from the last client's
    in-place-trained weights — measured in this harness, round-1 update
    ``0.1*g_last + 2*avg`` instead of ``avg``.  On GPU both artifacts
    disappear, so the published numbers are unaffected — but it is not the
    math to compare against.)"""
    env = dict(
        os.environ,
        REF_METRICS_OUT=metrics_out,
        PYTHONPATH=os.pathsep.join(
            [tree, os.path.join(REPO, "tools", "ref_shims")]),
        CUDA_VISIBLE_DEVICES="",
    )
    global _REF_RUN_SEQ
    proc = None
    for attempt in range(3):
        # fresh rendezvous port per invocation AND per attempt: a fixed
        # PID-derived port lands in TIME_WAIT between back-to-back
        # sequential torchruns of a multi-task run and the next rendezvous
        # fails flakily (observed: singles pass, sequences die on task 2+);
        # concurrent runs (pytest + manual) must not collide either
        _REF_RUN_SEQ += 1
        port = 20000 + (os.getpid() * 13 + _REF_RUN_SEQ * 101) % 20000
        cmd = [sys.executable, "-m", "torch.distributed.run",
               f"--nproc_per_node=2", f"--master-port={port}",
               os.path.join(REPO, "tools", "parity", "ref_launch.py"),
               "-dataPath", data_dir,
               "-outputPath", out_dir, "-config", cfg_path,
               "-task", task, "-backend", "gloo"]
        if os.path.exists(metrics_out):
            os.remove(metrics_out)  # a retry must not append to old metrics
        proc = subprocess.run(cmd, cwd=tree, env=env, capture_output=True,
                              text=True)
        if proc.returncode == 0:
            break
        sys.stderr.write(f"[parity] reference attempt {attempt + 1} failed "
                         f"rc={proc.returncode} (port {port}); tail:\n"
                         + proc.stdout[-2000:] + "\n" + proc.stderr[-3000:]
                         + "\n")
        # only rendezvous/bind flakiness justifies re-running a full
        # training; a deterministic crash (adapter bug, config typo)
        # would just burn two more identical multi-minute runs and bury
        # the real traceback.  NOTE "Connection closed by peer" is NOT
        # in this list: gloo prints it on rank0 for ANY rank1 crash.
        transient = ("Address already in use", "EADDRINUSE",
                     "failed to listen", "rendezvous")
        blob = proc.stdout + proc.stderr
        if not any(sig in blob for sig in transient):
            break
    if proc.returncode != 0:
        raise RuntimeError(f"reference trainer failed rc={proc.returncode}")
    return parse_ref_val_metrics(metrics_out)


def parse_ref_val_metrics(path):
    """Order-based alignment of a reference metrics.jsonl: Vals appear
    strictly in round order but the "Current iteration" marker flushes
    late (end-of-round metrics_payload), so align by ORDER — with
    initial_val on, the j-th val record is the state after j EVAL POINTS
    (round ``j * val_freq``; the parity harness runs val_freq=1 so j is
    the round directly, ``longrun.py`` rescales).  Shared by
    :func:`run_reference` and the longrun's reuse-from-disk path — ONE
    copy of the alignment logic."""
    rounds = {}
    j = {"Val loss": 0, "Val acc": 0}
    with open(path) as fh:
        for line in fh:
            rec = json.loads(line)
            name = rec.get("name")
            if name in j:
                rounds.setdefault(j[name], {})[name] = float(rec["value"])
                j[name] += 1
    return rounds


def run_msrflute(cfg_path, data_dir, out_dir, task, name_map=None,
                 env_override=None, timeout=None):
    """``name_map`` maps OUR metric names onto the canonical comparison
    keys ("Val loss"/"Val acc") — the personalization mode compares the
    reference's personalized Val metrics against our "Personalized val
    loss/acc" records.  ``env_override`` replaces env vars for this run:
    conv-heavy programs must drop to 2 virtual devices with
    single-threaded Eigen on this 1-core host, or XLA's in-process
    AllReduce rendezvous (hard 40 s termination, ``rendezvous.cc:127``)
    SIGABRTs when a starved device thread misses the collective.
    ``timeout`` (secs) kills the TRAINER ITSELF on expiry — a shell
    ``timeout`` around this call would kill only the orchestrator and
    orphan the trainer."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    env.update(env_override or {})
    cmd = [sys.executable, os.path.join(REPO, "e2e_trainer.py"),
           "-config", cfg_path, "-dataPath", data_dir,
           "-outputPath", out_dir, "-task", task]
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout[-4000:] + "\n" + proc.stderr[-6000:])
        raise RuntimeError(f"msrflute_tpu trainer failed rc={proc.returncode}")
    name_map = name_map or {"Val loss": "Val loss", "Val acc": "Val acc"}
    rounds = {}
    with open(os.path.join(out_dir, "log", "metrics.jsonl")) as fh:
        for line in fh:
            rec = json.loads(line)
            if rec.get("name") in name_map:
                rounds.setdefault(int(rec["step"]), {})[
                    name_map[rec["name"]]] = float(rec["value"])
    return rounds


# ----------------------------------------------------------------------
# orchestration
# ----------------------------------------------------------------------
TASKS = {
    # task: (shape, model classes, users, samples/user, batch, client_lr,
    #        data classes)
    # CNN: the reference model hardcodes 62 outputs (CNN_DropOut(False)),
    # but the synthetic blob only uses the first 10 labels with wide
    # separation — learnable at a dropout-gentle lr, so both trajectories
    # visibly descend instead of hovering at chance or diverging.
    "lr": ((784,), 10, 16, 32, 64, 0.1, 10),
    "cnn": ((28, 28), 62, 8, 48, 64, 0.05, 10),
    # LSTM: shape slot carries seq_len; "classes" is the model's hardcoded
    # vocab (90).  No dropout -> the trajectory is fully deterministic and
    # compared strictly, like LR (modulo deeper f32 recurrence noise).
    # lr=4.0: the protocol is exact full-batch SGD (1 batch/client, full
    # participation), which is stable at large lr and needs it — the
    # next-char rule only becomes learnable within ~100 rounds there
    # (probed offline; see ROUNDS_OVERRIDE).
    "lstm": ((24,), 90, 8, 16, 16, 4.0, None),
    # GRU (nlg_gru): shape = seq_len (== max_num_words), classes = vocab
    # (dims in GRU_DIMS); ONE utterance per user — the reference's
    # DynamicBatchSampler seeds its shuffle from wallclock randomness,
    # so only single-batch users are order-deterministic (its frames
    # budget == max_num_words then yields exactly one batch).  lr=1.0 is
    # stable full-batch (4.0 diverges — probed offline).
    # 48 users x 11 transitions must cover the 59-way next-word rule, or
    # val loss bottoms out early and rises (measured at 16 users: exact
    # tracking but the "loss halved" learning criterion fails on
    # overfitting, not on mismatch)
    "gru": ((12,), 60, 48, 1, 4, 1.0, None),
    # BERT (mlm_bert): shape = seq_len, classes = vocab (arch in
    # BERT_DIMS); pre-masked blobs (gen_bert_blob) + one shared local
    # checkpoint dir; lr probed offline (full-batch SGD on the pooled
    # data — see docstring protocol note)
    "bert": ((16,), 96, 8, 16, 16, 0.5, None),
}

# per-task default round counts, used when the caller leaves --rounds
# unset: single-local-step protocols can need more rounds to show
# learning (the reference runs exactly one epoch per round and
# multi-batch rounds would be shuffle-order-incomparable).  An explicit
# --rounds always wins (smoke tests pass --rounds 3).
DEFAULT_ROUNDS = 20
ROUNDS_BY_TASK = {"lstm": 100, "gru": 100, "bert": 30}


# ----------------------------------------------------------------------
# extension modes (review round 3 item 2): the same deterministic LR
# protocol with the reference's extensions switched ON — DGA softmax
# weighting, gradient quantization, and the local/global DP dance.
# ----------------------------------------------------------------------
def _dga_strategy(rc, tc):
    """Run DGA both sides (reference ``core/strategies/dga.py``; ours
    ``strategies/dga.py``): softmax client weight
    ``exp(-beta * train_loss / num_samples)`` with beta = softmax_beta.
    The base ref_config already carries aggregate_median/softmax_beta/
    weight_train_loss; FedAvg ignores them, DGA consumes them."""
    rc["strategy"] = "DGA"
    tc["strategy"] = "DGA"
    tc["server_config"]["aggregate_median"] = "softmax"
    tc["server_config"]["softmax_beta"] = 1.0
    tc["server_config"]["weight_train_loss"] = "train_loss"


def _quant(rc, tc, thresh=0.5, bits=8):
    """Gradient quantization (reference ``extensions/quantization/
    quant.py:9-50``, invoked from DGA's client payload, ``dga.py:148-149``):
    per-layer min/max binning into 2**bits levels, components with
    |g| <= quantile(|g|, thresh) zeroed.  The reference quantizes AFTER the
    weight multiply, we BEFORE — binning is scale-equivariant for w > 0
    (labels, bucket indices and the threshold all scale by w), so the two
    orders agree to f32 rounding."""
    for c in (rc, tc):
        c["client_config"]["quant_thresh"] = thresh
        c["client_config"]["quant_bits"] = bits


def _dp(rc, tc, *, eps, max_grad, max_weight=1.0, global_sigma=None):
    """Local (+optionally global) DP (reference ``extensions/privacy/
    __init__.py:154-201``): eps < 0 is CLIP-ONLY — fully deterministic;
    eps > 0 renormalizes the update to exactly max_grad norm, then adds
    Gaussian noise with sigma = sqrt(2 ln(1.25/delta)) * sensitivity/eps
    to [update, scaled weight] jointly, clamps the noised weight to
    [min_weight, max_weight] and unscales.  Huge eps -> vanishing sigma:
    the FULL eps>0 dance runs near-deterministically, so any semantic
    divergence (a wrong clamp, scale, or sensitivity) shows as trajectory
    drift while honest f32 noise stays tiny.  global_sigma=0.0 exercises
    the global-DP unroll/noise/update path exactly (noise*0)."""
    dp = {
        "enable_local_dp": True, "eps": eps, "delta": 1e-7,
        "max_grad": max_grad, "max_weight": max_weight,
        "min_weight": 1e-7, "weight_scaler": 1.0,
    }
    if global_sigma is not None:
        # must be > 0: the reference accountant computes (1/sigma)^2 and
        # crashes on exactly 0 (ZeroDivisionError at privacy/__init__.py:227;
        # its OverflowError for small sigma IS caught and logged as mu=-1)
        dp["enable_global_dp"] = True
        dp["global_sigma"] = global_sigma
    rc["dp_config"] = dict(dp)
    tc["dp_config"] = dict(dp)


def _personalization(rc, tc):
    """Personalization server both sides (reference ``core/client.py:
    387-443`` train path + ``:190-220`` eval path; ours
    ``engine/personalization.py``).  Alignment choices, each mirrored on
    both sides: local models cold-start from the SEED FILE (the
    reference's bare ``make_model`` random init is unreproducible — the
    parity_pers adapter loads pretrained_model_path, ours sets
    ``personalization_init: initial``); eval interpolates LOG-probs
    (``personalization_interp: logprobs``, the cv model contract); val
    data = the train blob so every val user owns a local model (the
    reference looks up ``<user>_model.tar`` by val-user NAME)."""
    rc["server_config"]["type"] = "personalization"
    rc["model_config"]["model_folder"] = "experiments/parity_pers/model.py"
    rc["client_config"]["convex_model_interp"] = 0.75
    rc["server_config"]["data_config"]["val"]["val_data"] = "train.json"
    rc["server_config"]["data_config"]["test"]["test_data"] = "train.json"
    tc["server_config"]["type"] = "personalization"
    tc["server_config"]["personalization_init"] = "initial"
    tc["server_config"]["personalization_interp"] = "logprobs"
    tc["client_config"]["convex_model_interp"] = 0.75
    tc["server_config"]["data_config"]["val"]["val_data"] = "train.json"
    tc["server_config"]["data_config"]["test"]["test_data"] = "train.json"


def _cnn_nodropout(rc, tc):
    """Dropout zeroed on both sides (reference: the ``parity_cnn_nd``
    adapter subclasses its CNN and sets both Dropout p=0; ours: the
    ``dropout1/dropout2`` model-config knobs).  The only RNG in the CNN
    family disappears, so the comparison is held to trajectory-exact."""
    rc["model_config"]["model_folder"] = "experiments/parity_cnn_nd/model.py"
    tc["model_config"]["dropout1"] = 0.0
    tc["model_config"]["dropout2"] = 0.0


MODES = {
    # deterministic: the CNN family with its one RNG source (dropout)
    # removed — upgrades the cnn entry from endpoint-grade to
    # trajectory-exact (review round 3 item 3)
    "cnn_nodropout": {"base": "cnn", "mutate": [_cnn_nodropout],
                      "criteria": "exact",
                      "tpu_env": {"XLA_FLAGS":
                                  "--xla_force_host_platform_device_count=2 "
                                  "--xla_cpu_multi_thread_eigen=false"}},
    # deterministic: per-user local models + convex-alpha interpolation
    # (compares the reference's personalized Val metrics against our
    # "Personalized val loss/acc" records)
    "pers": {"mutate": [_personalization], "criteria": "near",
             "tpu_metrics": {"Personalized val loss": "Val loss",
                             "Personalized val acc": "Val acc"}},
    # deterministic: UNEVEN user sizes under plain FedAvg — the
    # sample-count weights (reference fedavg.py:80: weight =
    # trainer.num_samples) stop cancelling in the normalized aggregate,
    # so proportional weighting itself is under test; every other family
    # ships equal-sized users
    "lr_uneven": {"mutate": [], "criteria": "exact", "uneven_users": True},
    # deterministic: non-trivial SERVER optimizers — every other family
    # runs the canonical SGD(lr=1.0) server step, so the ModelUpdater
    # semantics (our optax step vs the reference's torch.optim step on
    # the aggregated pseudo-gradient, core/trainer.py update_model) are
    # otherwise only exercised in their degenerate form.  torch Adam's
    # m_hat/(sqrt(v_hat)+eps) == optax.adam(eps_root=0); torch SGD
    # momentum buf = mu*buf + g == optax trace (nesterov off).
    "lr_server_adam": {
        "mutate": [lambda rc, tc: [
            c["server_config"].update(
                {"optimizer_config": {"type": "adam", "lr": 0.02}})
            for c in (rc, tc)]],
        "criteria": "exact"},
    "lr_server_momentum": {
        "mutate": [lambda rc, tc: [
            c["server_config"].update(
                {"optimizer_config": {"type": "sgd", "lr": 1.0,
                                      "momentum": 0.9}})
            for c in (rc, tc)]],
        "criteria": "exact"},
    # deterministic: CLIENT-side Adam — the per-client optimizer state
    # machinery (fresh optax.adam per round under vmap vs the
    # reference's fresh torch.optim.Adam per process_round) on real
    # bias-corrected first steps
    "lr_client_adam": {
        "mutate": [lambda rc, tc: [
            c["client_config"].update(
                {"optimizer_config": {"type": "adam", "lr": 0.05}})
            for c in (rc, tc)]],
        "criteria": "exact"},
    # deterministic: layer freezing — the aggregate skips the frozen
    # layer's pseudo-gradient (reference zeroes p.grad by exact
    # named_parameters match, fedavg.py:83-88 reading
    # model_config.freeze_layer; ours zeroes by flax path fragment from
    # client_config.freeze_layer) — each side names the SAME layer in
    # its own parameter vocabulary
    "lr_freeze": {
        "mutate": [lambda rc, tc: (
            rc["model_config"].update({"freeze_layer": "net.linear.weight"}),
            tc["client_config"].update({"freeze_layer": "Dense_0/kernel"}))],
        "criteria": "exact"},
    # deterministic: desired_max_samples BELOW the per-user sample count
    # with one batch per client — the reference's batch-granular cap
    # (loop-top check, core/trainer.py:363-364) means the full batch
    # still trains; an exact-sample cap would train on fewer samples
    # and shift both the pseudo-gradient and the num_samples weight
    "lr_maxsamples": {
        "mutate": [lambda rc, tc: [
            c["client_config"]["data_config"]["train"].update(
                {"desired_max_samples": 25}) for c in (rc, tc)]],
        "criteria": "exact"},
    # deterministic: best-model fallback + server momentum — the
    # reference reloads best_val_<criterion> EVERY val round
    # (server.py:475,561-571, unconditional), a no-op on improvement
    # (evaluation.run just overwrote best with current) and a rollback
    # otherwise; ours folds that into fall-back-iff-worse.  On this
    # protocol val improves monotonically (probed at lr 1/12 and with
    # momentum 0.95: the sigmoid LR never overshoots), so what this
    # family pins is the no-op-reload equivalence with live server
    # momentum state riding along — the rollback-on-worsening sub-path
    # remains covered by unit tests only.
    "lr_fallback": {
        "mutate": [lambda rc, tc: [
            (c["server_config"].update({"fall_back_to_best_model": True,
                                        "best_model_criterion": "loss",
                                        "initial_lr_client": 1.0,
                                        "optimizer_config": {
                                            "type": "sgd", "lr": 1.0,
                                            "momentum": 0.95}}),
             c["client_config"]["optimizer_config"].update({"lr": 1.0}))
            for c in (rc, tc)]],
        "criteria": "exact"},
    # deterministic: the LSTM family at a STABLE lr — the committed lstm
    # entry needs lr=4.0 for the rule to become learnable, which is
    # exactly where f32 chaos amplifies mid-trajectory (early-exact +
    # endpoint criteria); at lr=0.5 the dynamics contract and the deep
    # recurrence is held to pointwise agreement over the whole run
    "lstm_stable_lr": {
        "base": "lstm",
        "mutate": [lambda rc, tc: [
            (c["server_config"].update({"initial_lr_client": 0.5}),
             c["client_config"]["optimizer_config"].update({"lr": 0.5}))
            for c in (rc, tc)]],
        "criteria": "near"},
    # deterministic: DGA softmax weighting only
    "dga": {"mutate": [_dga_strategy], "criteria": "exact"},
    # DGA softmax weighting on the GRU base: exercises the
    # train_loss/num_samples metric where the COUNTING UNIT matters —
    # nlg_gru batches carry total_frames, so the reference counts WORDS
    # (core/trainer.py:402-403) while rows would be utterances; a
    # counting mismatch shifts every client's softmax weight even with
    # equal-sized users (unlike FedAvg, where a constant factor cancels
    # in the normalized aggregate)
    "gru_dga": {"base": "gru", "mutate": [_dga_strategy],
                "criteria": "near"},
    # deterministic: DGA + per-layer 8-bit quantization at the 0.5 quantile
    "dga_quant": {"mutate": [_dga_strategy, _quant], "criteria": "near"},
    # deterministic: the same transforms over CONV pseudo-gradients —
    # 4-D kernel tensors exercise per-layer min/max binning and the
    # |g|-quantile threshold on shapes the LR base never produces
    # (dropout zeroed so the conv family stays deterministic)
    "cnn_dga_quant": {"base": "cnn",
                      "mutate": [_cnn_nodropout, _dga_strategy, _quant],
                      "criteria": "near",
                      "tpu_env": {"XLA_FLAGS":
                                  "--xla_force_host_platform_device_count=2 "
                                  "--xla_cpu_multi_thread_eigen=false"}},
    # deterministic: clip-only local DP (eps < 0) under DGA
    "dp_clip": {"mutate": [_dga_strategy,
                           lambda rc, tc: _dp(rc, tc, eps=-1.0,
                                              max_grad=0.05)],
                "criteria": "near"},
    # near-deterministic: the full eps>0 dance at vanishing sigma, plus
    # the global-DP path at near-zero sigma (exactly 0 crashes the
    # reference accountant; 1e-4 keeps the added noise ~1e-4 relative).
    # max_grad must be SMALL: the eps>0 path renormalizes every update to
    # exactly max_grad norm, so a large value forces constant big steps
    # that blow the sigmoid-output LR up to inf loss -> every weight
    # filtered to 0 -> the reference divides by zero clients (measured at
    # max_grad=0.5, round ~8)
    "dp_tiny_noise": {"mutate": [_dga_strategy,
                                 lambda rc, tc: _dp(rc, tc, eps=1e8,
                                                    max_grad=0.05,
                                                    global_sigma=1e-4)],
                      "criteria": "near"},
    # statistical: real noise, RNG incomparable across torch/jax — the
    # criterion is an envelope (both learn; endpoints in a band)
    "dp_envelope": {"mutate": [_dga_strategy,
                               lambda rc, tc: _dp(rc, tc, eps=1000.0,
                                                  max_grad=0.05,
                                                  global_sigma=0.1)],
                    "criteria": "envelope"},
}


def _judge_mode(traj, criteria):
    """ok/verdict for an extension mode run on the deterministic LR base."""
    diffs_loss = [r["Val loss"]["abs_diff"] for r in traj
                  if r["Val loss"]["abs_diff"] is not None]
    diffs_acc = [r["Val acc"]["abs_diff"] for r in traj
                 if r["Val acc"]["abs_diff"] is not None]
    max_dl = max(diffs_loss) if diffs_loss else None
    max_da = max(diffs_acc) if diffs_acc else None
    ok, verdict = False, "insufficient data"
    if max_dl is None or max_da is None or not traj:
        return ok, verdict, max_dl, max_da
    if criteria == "exact":
        ok = max_dl < 1e-4 and max_da == 0.0
        verdict = ("trajectory-exact (f32 accumulation noise only)" if ok
                   else "MISMATCH beyond float noise")
    elif criteria == "near":
        # deterministic payload transforms, but with hard nonlinearities
        # (quant bin edges, clip thresholds) that can amplify one-ulp
        # disagreements into a visible-but-bounded wobble
        ok = max_dl < 5e-3 and max_da <= 0.02
        verdict = ("trajectory matched within transform-boundary noise"
                   if ok else "MISMATCH beyond transform-boundary noise")
    else:  # envelope
        ref0 = traj[0]["Val loss"]["reference"]
        fin = traj[-1]
        rl = fin["Val loss"]["reference"]
        tl = fin["Val loss"]["msrflute_tpu"]
        ra = fin["Val acc"]["reference"]
        ta = fin["Val acc"]["msrflute_tpu"]
        if None not in (ref0, rl, tl, ra, ta):
            learned = rl < 0.8 * ref0 and tl < 0.8 * ref0
            ok = (learned
                  and (abs(rl - tl) < 0.15
                       or abs(rl - tl) / max(rl, tl) < 0.15)
                  and abs(ra - ta) < 0.1)
        verdict = ("both learn under matched DP noise scale; endpoints "
                   "in statistical envelope" if ok
                   else "MISMATCH beyond DP statistical envelope")
    return ok, verdict, max_dl, max_da


def run_task(task, rounds, scratch, mode=None):
    shape, classes, users, samples, batch, lr, data_classes = TASKS[task]
    if rounds is None:
        rounds = ROUNDS_BY_TASK.get(task, DEFAULT_ROUNDS)
    rng = np.random.default_rng(7)
    work = os.path.join(scratch, mode or task)
    shutil.rmtree(work, ignore_errors=True)
    data_ref = os.path.join(work, "data_ref")
    data_tpu = os.path.join(work, "data_tpu")
    os.makedirs(data_ref)
    os.makedirs(data_tpu)

    if task == "lstm":
        seq_len = shape[0]
        trans = rng.permutation(np.arange(1, classes))
        train = gen_lstm_blob(rng, users, samples, seq_len, vocab=classes,
                              trans=trans)
        val = gen_lstm_blob(rng, 4, 32, seq_len, vocab=classes, trans=trans)
        # int sequences need no layout conversion between the frameworks
        for blob, name in ((train, "train.json"), (val, "val.json")):
            write_blob(blob, os.path.join(data_ref, name))
            write_blob(blob, os.path.join(data_tpu, name))
        init = lstm_init(rng, vocab=classes)
        save_torch_lstm(init, os.path.join(work, "init.pt"))
        save_flax_lstm(init, os.path.join(work, "init.msgpack"))
    elif task == "bert":
        seq_len = shape[0]
        perm = rng.permutation(classes - 5)
        train = gen_bert_blob(rng, users, samples, seq_len, vocab=classes,
                              perm=perm)
        val = gen_bert_blob(rng, 4, 32, seq_len, vocab=classes, perm=perm)
        for blob, name in ((train, "train.json"), (val, "val.json")):
            write_blob(blob, os.path.join(data_ref, name))
            write_blob(blob, os.path.join(data_tpu, name))
        # one torch-saved checkpoint dir IS the identical init (both
        # sides' pretrained loaders point at it)
        bert_ckpt = make_bert_checkpoint(work, vocab=classes,
                                         hidden=BERT_DIMS["hidden_size"],
                                         layers=BERT_DIMS["num_hidden_layers"],
                                         heads=BERT_DIMS["num_attention_heads"],
                                         intermediate=BERT_DIMS["intermediate_size"])
    elif task == "gru":
        seq_len = shape[0]
        trans = rng.permutation(np.arange(1, classes))
        train = gen_gru_blob(rng, users, seq_len, vocab=classes,
                             trans=trans)
        val = gen_gru_blob(rng, 16, seq_len, vocab=classes, trans=trans)
        for blob, name in ((train, "train.json"), (val, "val.json")):
            write_gru_blob(blob, os.path.join(data_ref, name))
            write_gru_blob(blob, os.path.join(data_tpu, name))
        write_vocab(os.path.join(work, "vocab.txt"), classes)
        init = gru_init(rng, vocab=classes, embed=GRU_DIMS["embed_dim"],
                        hidden=GRU_DIMS["hidden_dim"])
        save_torch_gru(init, os.path.join(work, "init.pt"))
        save_flax_gru(init, os.path.join(work, "init.msgpack"))
    else:
        means = rng.normal(size=(data_classes,) + shape).astype(np.float32)
        if mode is not None and MODES[mode].get("uneven_users"):
            # spread 8..(8+3(users-1)) — stays under the one-batch cap
            # (batch_size 64) so rounds remain shuffle-order-comparable
            samples = [8 + 3 * u for u in range(users)]
        train = gen_blob(rng, users, samples, shape, data_classes, sep=3.0,
                         means=means)
        val = gen_blob(rng, 4, 64, shape, data_classes, sep=3.0, means=means)
        # the reference __getitem__ transposes images; pre-swap its copy so
        # both frameworks train on identical tensors
        for blob, name in ((train, "train.json"), (val, "val.json")):
            write_blob(blob, os.path.join(data_ref, name),
                       transpose_images=True)
            write_blob(blob, os.path.join(data_tpu, name),
                       transpose_images=False)

        if task == "lr":
            init = lr_init(rng, 784, classes)
            save_torch_lr(init, os.path.join(work, "init.pt"))
            save_flax_lr(init, os.path.join(work, "init.msgpack"))
        else:
            init = cnn_init(rng, classes)
            save_torch_cnn(init, os.path.join(work, "init.pt"))
            save_flax_cnn(init, os.path.join(work, "init.msgpack"))

    import yaml
    tree = build_ref_tree(scratch)
    outdim = shape[0] if task in ("lstm", "gru") else classes  # seq_len
    if task == "bert":
        # one shared checkpoint DIR is the init for both sides
        ref_init = tpu_init = bert_ckpt
    else:
        ref_init = os.path.join(work, "init.pt")
        tpu_init = os.path.join(work, "init.msgpack")
    rc = ref_config(task, rounds, users, batch, lr, ref_init, outdim)
    tc = tpu_config(task, rounds, users, batch, lr, tpu_init, outdim)
    if mode is not None:
        for mutate in MODES[mode]["mutate"]:
            mutate(rc, tc)
    if task == "gru":
        # the nlg_gru loaders read their knobs from the per-split data
        # blocks: plain-txt vocab (absolute path), frames budget ==
        # max_num_words (-> one utterance per batch), preencoded int rows
        gru_keys = {"vocab_dict": os.path.join(work, "vocab.txt"),
                    "max_num_words": shape[0], "pin_memory": False,
                    "unsorted_batch": True}
        rc["server_config"]["data_config"]["val"].update(gru_keys)
        rc["server_config"]["data_config"]["test"].update(gru_keys)
        rc["client_config"]["data_config"]["train"].update(gru_keys)
        # our side tokenizes through the SAME vocab file
        tc["model_config"]["vocab_dict"] = os.path.join(work, "vocab.txt")
    ref_cfg = os.path.join(work, "ref.yaml")
    tpu_cfg = os.path.join(work, "tpu.yaml")
    with open(ref_cfg, "w") as fh:
        yaml.safe_dump(rc, fh)
    with open(tpu_cfg, "w") as fh:
        yaml.safe_dump(tc, fh)

    print(f"[parity:{task}] running reference (torch, 2-process gloo)...")
    ref = run_reference(tree, ref_cfg, data_ref,
                        os.path.join(work, "out_ref"), f"parity_{task}",
                        os.path.join(work, "ref_metrics.jsonl"))
    print(f"[parity:{task}] running msrflute_tpu (8-dev virtual cpu mesh)...")
    tpu_name_map, tpu_env = None, None
    if mode is not None:
        tpu_name_map = MODES[mode].get("tpu_metrics")
        tpu_env = MODES[mode].get("tpu_env")
    tpu = run_msrflute(tpu_cfg, data_tpu, os.path.join(work, "out_tpu"),
                       f"parity_{task}", name_map=tpu_name_map,
                       env_override=tpu_env)

    common = sorted(set(ref) & set(tpu))
    traj = []
    for r in common:
        row = {"round": r}
        for key in ("Val loss", "Val acc"):
            rv, tv = ref[r].get(key), tpu[r].get(key)
            row[key] = {"reference": rv, "msrflute_tpu": tv,
                        "abs_diff": (abs(rv - tv)
                                     if rv is not None and tv is not None
                                     else None)}
        traj.append(row)
    diffs_loss = [row["Val loss"]["abs_diff"] for row in traj
                  if row["Val loss"]["abs_diff"] is not None]
    diffs_acc = [row["Val acc"]["abs_diff"] for row in traj
                 if row["Val acc"]["abs_diff"] is not None]
    max_dl = max(diffs_loss) if diffs_loss else None
    max_da = max(diffs_acc) if diffs_acc else None
    if mode is not None:
        ok, verdict, _, _ = _judge_mode(traj, MODES[mode]["criteria"])
    elif task == "bert":
        # fully deterministic protocol (pre-masked data, zero dropout in
        # the saved config, sequential order): held to trajectory
        # exactness within an f32 band.  The review round 3 scope for this
        # family is a short deterministic trajectory + transplant
        # forward-exactness — NOT a learning demonstration: the 2-layer
        # 32-wide model cannot learn the 91-way permutation rule in tens
        # of full-batch steps (probed offline with torch SGD and Adam at
        # 5 lrs; val stays at the ln(91) chance floor while train loss
        # moves), so the criterion instead demands material MOVEMENT
        # (the dynamics are exercised) plus pointwise agreement.
        ref0 = traj[0]["Val loss"]["reference"] if traj else None
        rl = traj[-1]["Val loss"]["reference"] if traj else None
        moved = (ref0 is not None and rl is not None
                 and abs(rl - ref0) > 5e-3)
        ok = (max_dl is not None and max_dl < 5e-3
              and max_da is not None and max_da <= 0.02 and moved)
        verdict = ("trajectory matched within f32 band; dynamics "
                   "exercised (loss moves materially)" if ok
                   else "MISMATCH beyond f32 band (or no movement)")
    elif task == "lr":
        # fully deterministic protocol: must be trajectory-exact
        ok = max_dl is not None and max_dl < 1e-4 and max_da == 0.0
        verdict = ("trajectory-exact (float32 accumulation noise only)"
                   if ok else "MISMATCH beyond float noise")
    elif task in ("lstm", "gru"):
        # no dropout -> fully deterministic, but chaotically SENSITIVE:
        # measured on this protocol (committed PARITY.json), the sides
        # agree to < 1e-3 for the first ~30 rounds (pure f32
        # accumulation-order noise), then the steep-descent phase
        # amplifies that noise exponentially — pointwise gaps transiently
        # reach O(1) mid-descent (1.45 at round 67 in the committed run,
        # where the two sides cross the cliff a few rounds apart) — and
        # the gap CONTRACTS again as both converge (0.08 by round 100).
        # That grow-then-recontract shape is the signature of trajectory
        # sensitivity, not of a semantic difference (a wrong lr or
        # denominator would drift proportionally from round 1).  Honest
        # criteria, mirroring the CNN rationale: the early phase is
        # strictly exact, both sides learn the next-char rule, and the
        # endpoints match.
        early = [row["Val loss"]["abs_diff"] for row in traj[:26]
                 if row["Val loss"]["abs_diff"] is not None]
        ref0 = traj[0]["Val loss"]["reference"] if traj else None
        a0r = traj[0]["Val acc"]["reference"] if traj else None
        a0t = traj[0]["Val acc"]["msrflute_tpu"] if traj else None
        fin = traj[-1] if traj else None
        rl = (fin or {}).get("Val loss", {}).get("reference")
        tl = (fin or {}).get("Val loss", {}).get("msrflute_tpu")
        ra = (fin or {}).get("Val acc", {}).get("reference")
        ta = (fin or {}).get("Val acc", {}).get("msrflute_tpu")
        ok = False
        if early and None not in (ref0, a0r, a0t, rl, tl, ra, ta):
            # "both learned" must respect the task's entropy floor: the
            # noisy next-token rules have irreducible CE (noise entropy +
            # the unpredictable first token), so demand a clear loss drop
            # AND a decisive accuracy gain rather than an arbitrary
            # loss-halving (measured: gru converges to ~2.3 from 4.1 at
            # 72% accuracy — halving is unreachable there by design)
            learned = (rl < 0.8 * ref0 and tl < 0.8 * ref0
                       and ra - a0r > 0.25 and ta - a0t > 0.25)
            ok = (max(early) < 5e-3
                  and learned
                  # absolute-or-relative: near-zero converged losses make
                  # a pure relative test divide by ~0 (CNN branch ditto)
                  and (abs(rl - tl) < 0.05
                       or abs(rl - tl) / max(rl, tl) < 0.1)
                  and abs(ra - ta) < 0.05)
        verdict = ("early-trajectory exact (f32 noise only); both learn "
                   "the rule; endpoints matched within chaotic-"
                   "sensitivity noise" if ok
                   else "MISMATCH beyond deterministic-sensitivity criteria")
    else:
        # CNN has torch/jax-incomparable dropout RNG, and during the steep
        # descent phase a small RNG-induced time offset yields large
        # pointwise loss gaps — so a max-abs-diff band is the wrong
        # metric.  The honest criteria: round 0 (dropout inactive) exact,
        # both trajectories actually LEARN (final loss well below round 0),
        # and the endpoints agree (relative loss diff + acc diff small).
        r0 = traj[0]["Val loss"]["abs_diff"] if traj else None
        fin = traj[-1] if traj else None
        ref0 = traj[0]["Val loss"]["reference"] if traj else None
        ok = False
        vals = ((fin or {}).get("Val loss", {}), (fin or {}).get("Val acc", {}))
        rl, tl = vals[0].get("reference"), vals[0].get("msrflute_tpu")
        ra, ta = vals[1].get("reference"), vals[1].get("msrflute_tpu")
        if None not in (r0, ref0, rl, tl, ra, ta):
            # endpoints agree: absolute OR relative — near-converged losses
            # (both ~1e-3) make a pure relative test meaningless
            close = (abs(rl - tl) < 0.05
                     or abs(rl - tl) / max(rl, tl) < 0.05)
            ok = (r0 < 1e-4
                  and rl < 0.8 * ref0 and tl < 0.8 * ref0   # both learned
                  and close
                  and abs(ra - ta) < 0.08)
        verdict = ("round-0 exact; both learn; endpoints matched within "
                   "dropout noise" if ok
                   else "MISMATCH beyond dropout-noise criteria")
    protocol = {"users": users, "samples_per_user": samples,
                "batch_size": batch, "client_lr": lr,
                "rounds": rounds, "classes": classes,
                "local_steps_per_round": 1,
                "full_participation": True,
                "identical_init": True}
    if mode is not None:
        protocol["mode"] = mode
        protocol["strategy"] = rc["strategy"]
        protocol["dp_config"] = rc.get("dp_config")
        protocol["quant_thresh"] = rc["client_config"].get("quant_thresh")
        protocol["quant_bits"] = rc["client_config"].get("quant_bits")
        protocol["criteria"] = MODES[mode]["criteria"]
    return {
        "task": f"{task}+{mode}" if mode else task,
        "protocol": protocol,
        "rounds_compared": len(traj),
        "max_abs_diff_val_loss": max_dl,
        "max_abs_diff_val_acc": max_da,
        "ok": ok,
        "verdict": verdict,
        "final": traj[-1] if traj else None,
        "trajectory": traj,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", default="lr,cnn,lstm,gru")
    ap.add_argument("--rounds", type=int, default=None,
                    help="override every task's round count "
                         "(default: per-task, see ROUNDS_BY_TASK)")
    ap.add_argument("--scratch", default="/tmp/parity_scratch")
    ap.add_argument("--out", default=os.path.join(REPO, "PARITY.json"))
    ap.add_argument("--merge", action="store_true",
                    help="update only --tasks entries in an existing "
                         "--out instead of overwriting the whole file")
    args = ap.parse_args()

    os.makedirs(args.scratch, exist_ok=True)
    results = {}
    if args.merge and os.path.exists(args.out):
        with open(args.out) as fh:
            results = json.load(fh)
    for task in args.tasks.split(","):
        task = task.strip()
        if task in MODES:  # extension mode riding a deterministic base
            results[task] = run_task(MODES[task].get("base", "lr"),
                                     args.rounds, args.scratch, mode=task)
        else:
            results[task] = run_task(task, args.rounds, args.scratch)
        r = results[task]
        print(f"[parity:{task}] rounds={r['rounds_compared']} "
              f"max|dloss|={r['max_abs_diff_val_loss']} "
              f"max|dacc|={r['max_abs_diff_val_acc']} ok={r['ok']}")
        # write after EVERY task: a flaky later task must not lose the
        # finished families of a long multi-task run
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
