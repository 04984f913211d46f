"""Cross-framework parity: the ACTUAL reference (torch, /root/reference)
vs msrflute_tpu on identical blobs + identical init (review round 2 item 3).

The full 20-round artifact is PARITY.json (tools/parity/run_parity.py);
this test runs the deterministic LR protocol for 3 rounds so the claim
stays continuously verified.  Skips when the reference mount is absent.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_lstm_weight_transplant_forward_exact(tmp_path):
    """The torch-LSTM -> flax-OptimizedLSTMCell transplant (gate slicing,
    kernel transposes, bias summing) must produce the same forward loss on
    the same batch — the foundation of the recurrent parity comparison.
    Runs without the reference mount: the torch side is the same standard
    nn.Embedding/nn.LSTM/nn.Linear architecture the reference hardcodes
    (experiments/nlp_rnn_fedshakespeare/model.py:12-40)."""
    import numpy as np
    torch = pytest.importorskip("torch")
    from torch import nn

    sys.path.insert(0, os.path.join(REPO, "tools", "parity"))
    from run_parity import (gen_lstm_blob, lstm_init, save_flax_lstm,
                            save_torch_lstm)

    init = lstm_init(np.random.default_rng(3))
    pt, mp = str(tmp_path / "i.pt"), str(tmp_path / "i.msgpack")
    save_torch_lstm(init, pt)
    save_flax_lstm(init, mp)

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.embeddings = nn.Embedding(90, 8, padding_idx=0)
            self.lstm = nn.LSTM(8, 256, num_layers=2, batch_first=True)
            self.fc = nn.Linear(256, 90)

        def forward(self, x):
            out, _ = self.lstm(self.embeddings(x))
            return torch.transpose(self.fc(out), 1, 2)

    net = Net()
    sd = torch.load(pt)
    net.load_state_dict({k[len("net."):]: v for k, v in sd.items()})

    blob = gen_lstm_blob(np.random.default_rng(5), 1, 4, 24)
    x = np.asarray(blob["user_data"]["0000"]["x"])
    y = np.asarray(blob["user_data_label"]["0000"])
    with torch.no_grad():
        loss_t = float(nn.CrossEntropyLoss(ignore_index=0)(
            net(torch.tensor(x)), torch.tensor(y).long()))

    import jax
    import jax.numpy as jnp
    from flax import serialization

    from msrflute_tpu.config import ModelConfig
    from msrflute_tpu.models import make_task
    task = make_task(ModelConfig(model_type="LSTM",
                                 extra={"vocab_size": 90, "seq_len": 24}))
    params = task.init_params(jax.random.PRNGKey(0))
    with open(mp, "rb") as fh:
        params = serialization.from_state_dict(
            params, serialization.msgpack_restore(fh.read()))
    batch = {"x": jnp.asarray(x, jnp.int32), "y": jnp.asarray(y, jnp.int32),
             "sample_mask": jnp.ones((4,), jnp.float32)}
    loss_j = float(task.loss(params, batch, jax.random.PRNGKey(0), False)[0])
    assert abs(loss_t - loss_j) < 1e-5, (loss_t, loss_j)


def test_gru_weight_transplant_forward_exact(tmp_path):
    """The torch-GRU2 -> flax _ConvexGRUCell transplant (stacked r/i/n
    gates, kernel transposes, tied embedding + squeeze) must produce the
    same forward loss on the same batch — including the reference's
    initial-zero-state prediction of token 0
    (SequenceLMTask.ref_initial_prediction).  The torch side replicates
    the reference architecture (experiments/nlg_gru/model.py:11-83)
    with standard modules, so no reference mount is needed."""
    import numpy as np
    torch = pytest.importorskip("torch")
    from torch import nn

    sys.path.insert(0, os.path.join(REPO, "tools", "parity"))
    from run_parity import GRU_DIMS, gru_init, save_flax_gru, save_torch_gru

    V, E, H, L = (GRU_DIMS["vocab_size"], GRU_DIMS["embed_dim"],
                  GRU_DIMS["hidden_dim"], 12)
    init = gru_init(np.random.default_rng(3), V, E, H)
    pt, mp = str(tmp_path / "g.pt"), str(tmp_path / "g.msgpack")
    save_torch_gru(init, pt)
    save_flax_gru(init, mp)

    class GRU2(nn.Module):
        def __init__(self):
            super().__init__()
            self.w_ih = nn.Linear(E, 3 * H, True)
            self.w_hh = nn.Linear(H, 3 * H, True)

        def forward(self, inp):
            hiddens = [torch.zeros((inp.shape[0], H))]
            for t in range(inp.shape[1]):
                g_i = self.w_ih(inp[:, t])
                g_h = self.w_hh(hiddens[-1])
                i_r, i_i, i_n = g_i.chunk(3, 1)
                h_r, h_i, h_n = g_h.chunk(3, 1)
                r = torch.sigmoid(i_r + h_r)
                i = torch.sigmoid(i_i + h_i)
                n = torch.tanh(i_n + r * h_n)
                hiddens.append(n + i * (hiddens[-1] - n))
            return torch.stack(hiddens, dim=1)

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.table = nn.Parameter(torch.zeros((V, E)))
            self.unembedding_bias = nn.Parameter(torch.zeros(V))
            self.rnn = GRU2()
            self.squeeze = nn.Linear(H, E, bias=False)

        def forward(self, x):
            hid = self.rnn(nn.functional.embedding(x, self.table))
            return self.squeeze(hid) @ self.table.t() + \
                self.unembedding_bias

    net = Net()
    sd = torch.load(pt)
    net.load_state_dict({
        "table": sd["embedding.table"],
        "unembedding_bias": sd["embedding.unembedding_bias"],
        "rnn.w_ih.weight": sd["rnn.w_ih.weight"],
        "rnn.w_ih.bias": sd["rnn.w_ih.bias"],
        "rnn.w_hh.weight": sd["rnn.w_hh.weight"],
        "rnn.w_hh.bias": sd["rnn.w_hh.bias"],
        "squeeze.weight": sd["squeeze.weight"]})
    x = np.random.default_rng(5).integers(1, V, size=(4, L))
    xt = torch.tensor(x)
    with torch.no_grad():
        out = net(xt[:, :-1])  # [B, L, V] incl. the h0 prediction
        loss_t = float(nn.functional.cross_entropy(
            out.reshape(-1, V), xt.reshape(-1)))

    import jax
    import jax.numpy as jnp
    from flax import serialization

    from msrflute_tpu.config import ModelConfig
    from msrflute_tpu.models import make_task
    task = make_task(ModelConfig(model_type="GRU", extra=dict(
        GRU_DIMS, max_num_words=L)))
    params = task.init_params(jax.random.PRNGKey(0))
    with open(mp, "rb") as fh:
        params = serialization.from_state_dict(
            params, serialization.msgpack_restore(fh.read()))
    batch = {"x": jnp.asarray(x, jnp.int32),
             "sample_mask": jnp.ones((4,), jnp.float32)}
    loss_j = float(task.loss(params, batch, jax.random.PRNGKey(0),
                             False)[0])
    assert abs(loss_t - loss_j) < 1e-5, (loss_t, loss_j)


@pytest.mark.skipif(not os.path.isdir("/root/reference"),
                    reason="reference mount not available")
def test_lr_trajectory_exact(tmp_path):
    out = tmp_path / "parity.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parity",
                                      "run_parity.py"),
         "--tasks", "lr", "--rounds", "3",
         "--scratch", str(tmp_path / "scratch"), "--out", str(out)],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(out.read_text())["lr"]
    assert res["ok"], res["verdict"]
    assert res["rounds_compared"] >= 3
    assert res["max_abs_diff_val_loss"] < 1e-4
    assert res["max_abs_diff_val_acc"] == 0.0


def test_bert_checkpoint_forward_exact(tmp_path):
    """Both frameworks load ONE local torch-saved tiny-BERT checkpoint dir
    (the reference via its model_name_or_path pretrained path,
    ``/root/reference/experiments/mlm_bert/model.py:119-123``; ours via the
    same config key with HF's torch->flax conversion) and must produce the
    same masked-LM loss on the same pre-masked batch (review round 3 item 4).
    Runs without the reference mount: the torch side is the same HF
    ``BertForMaskedLM`` the reference wraps."""
    import numpy as np
    torch = pytest.importorskip("torch")

    sys.path.insert(0, os.path.join(REPO, "tools", "parity"))
    from run_parity import BERT_DIMS, gen_bert_blob, make_bert_checkpoint

    rng = np.random.default_rng(11)
    V, L = BERT_DIMS["vocab_size"], 16
    ckpt = make_bert_checkpoint(str(tmp_path), vocab=V,
                                hidden=BERT_DIMS["hidden_size"],
                                layers=BERT_DIMS["num_hidden_layers"],
                                heads=BERT_DIMS["num_attention_heads"],
                                intermediate=BERT_DIMS["intermediate_size"])
    blob = gen_bert_blob(rng, 1, 8, L, vocab=V)
    x = np.asarray(blob["user_data"]["0000"]["x"])
    y = np.asarray(blob["user_data_label"]["0000"])

    from transformers import BertForMaskedLM
    net = BertForMaskedLM.from_pretrained(ckpt)
    with torch.no_grad():
        loss_t = float(net(input_ids=torch.tensor(x),
                           attention_mask=torch.ones_like(torch.tensor(x)),
                           labels=torch.tensor(y)).loss)

    import jax
    import jax.numpy as jnp

    from msrflute_tpu.config import ModelConfig
    from msrflute_tpu.models import make_task
    task = make_task(ModelConfig(model_type="BERT", extra={
        "BERT": {"model": {"model_name_or_path": ckpt,
                           "max_seq_length": L, "mask_token_id": 4,
                           "premasked": True},
                 "training": {"seed": 0, "label_smoothing_factor": 0}}}))
    params = task.init_params(jax.random.PRNGKey(0))
    batch = {"x": jnp.asarray(x, jnp.int32), "y": jnp.asarray(y, jnp.int32),
             "sample_mask": jnp.ones((len(x),), jnp.float32)}
    loss_j = float(task.loss(params, batch, jax.random.PRNGKey(0),
                             False)[0])
    assert abs(loss_t - loss_j) < 1e-5, (loss_t, loss_j)


@pytest.mark.skipif(not os.path.isdir("/root/reference"),
                    reason="reference mount absent")
def test_resnet_gn_transplant_forward_exact():
    """GN-configured ResNet cross-check (review round 3 item 6): build the
    REFERENCE ResNet with group_norm actually honored
    (``ResNet(BasicBlock, [2,2,2,2], num_classes, group_norm=32)`` —
    the experiment wrapper ignores its config and calls bare
    ``resnet18()``, ``experiments/cv_resnet_fedcifar100/model.py:139-152``),
    transplant its weights into our flax ResNet and demand identical
    logits.  Transplant notes: the reference GroupNorm affine is
    per-GROUP (weight shape c/32, ``group_normalization.py:104-112``) —
    repeated across each group's channels for our per-channel params;
    conv [O,I,kh,kw] -> [kh,kw,I,O]; fc transposed.  Full-trajectory
    parity is out of scope BY STRUCTURE: per-group affine receives the
    summed per-channel gradient, so the two parameterizations diverge
    from the first update (docs/reference_quirks.md)."""
    import numpy as np
    torch = pytest.importorskip("torch")
    from importlib.machinery import SourceFileLoader

    ref_dir = "/root/reference/experiments/cv_resnet_fedcifar100"
    # model.py does `from experiments.cv_resnet_fedcifar100.group_
    # normalization import ...` — needs the reference root as package
    # root; importing the experiments package pulls reference utils,
    # whose offline deps (easydict et al.) live in tools/ref_shims
    sys.path.insert(0, "/root/reference")
    sys.path.insert(0, os.path.join(REPO, "tools", "ref_shims"))
    loader = SourceFileLoader(
        "ref_resnet_model", os.path.join(ref_dir, "model.py"))
    mod = loader.load_module()

    torch.manual_seed(0)
    net = mod.ResNet(mod.BasicBlock, [2, 2, 2, 2], num_classes=10,
                     group_norm=32)
    net.eval()

    import jax
    import jax.numpy as jnp

    from msrflute_tpu.config import ModelConfig
    from msrflute_tpu.models import make_task
    task = make_task(ModelConfig(model_type="RESNET", extra={
        "num_classes": 10, "image_size": 32}))
    params = jax.device_get(task.init_params(jax.random.PRNGKey(0)))

    def conv(w):
        return np.asarray(w.detach()).transpose(2, 3, 1, 0)

    def gn(w, channels):
        w = np.asarray(w.detach())
        return np.repeat(w, channels // len(w))

    sd = net.state_dict()
    p = params
    p["Conv_0"]["kernel"] = conv(sd["conv1.weight"])
    p["GroupNorm_0"]["scale"] = gn(sd["bn1.weight"], 64)
    p["GroupNorm_0"]["bias"] = gn(sd["bn1.bias"], 64)
    planes, bi = 64, 0
    for stage in range(4):
        for block in range(2):
            t = f"layer{stage + 1}.{block}"
            fb = p[f"_BasicBlock_{bi}"]
            fb["Conv_0"]["kernel"] = conv(sd[f"{t}.conv1.weight"])
            fb["GroupNorm_0"]["scale"] = gn(sd[f"{t}.bn1.weight"], planes)
            fb["GroupNorm_0"]["bias"] = gn(sd[f"{t}.bn1.bias"], planes)
            fb["Conv_1"]["kernel"] = conv(sd[f"{t}.conv2.weight"])
            fb["GroupNorm_1"]["scale"] = gn(sd[f"{t}.bn2.weight"], planes)
            fb["GroupNorm_1"]["bias"] = gn(sd[f"{t}.bn2.bias"], planes)
            if f"{t}.downsample.0.weight" in sd:
                fb["Conv_2"]["kernel"] = conv(sd[f"{t}.downsample.0.weight"])
                fb["GroupNorm_2"]["scale"] = gn(
                    sd[f"{t}.downsample.1.weight"], planes)
                fb["GroupNorm_2"]["bias"] = gn(
                    sd[f"{t}.downsample.1.bias"], planes)
            bi += 1
        planes = planes * 2 if stage < 3 else planes
    p["Dense_0"]["kernel"] = np.asarray(sd["fc.weight"].detach()).T
    p["Dense_0"]["bias"] = np.asarray(sd["fc.bias"].detach())

    x = np.random.default_rng(0).normal(size=(4, 32, 32, 3)).astype(
        np.float32)
    with torch.no_grad():
        logits_t = net(torch.tensor(x.transpose(0, 3, 1, 2))).numpy()
    logits_j = np.asarray(task.apply(p, jnp.asarray(x)))
    np.testing.assert_allclose(logits_j, logits_t, atol=2e-4, rtol=2e-4)


@pytest.mark.skipif(not os.path.isdir("/root/reference"),
                    reason="reference mount not available")
def test_dga_extension_mode_trajectory_exact(tmp_path):
    """Extension-mode regression: the DGA softmax-weighting mode (the
    base of all five extensions-ON PARITY.json families) stays
    trajectory-exact against the actual reference at 2 rounds — keeps
    the round-4 extension-parity claim continuously verified the same
    way test_lr_trajectory_exact pins the plain family."""
    out = tmp_path / "parity.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parity",
                                      "run_parity.py"),
         "--tasks", "dga", "--rounds", "2",
         "--scratch", str(tmp_path / "scratch"), "--out", str(out)],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(out.read_text())["dga"]
    assert res["ok"], res["verdict"]
    assert res["protocol"]["strategy"] == "DGA"
    assert res["max_abs_diff_val_loss"] < 1e-4
    assert res["max_abs_diff_val_acc"] == 0.0


@pytest.mark.skipif(not os.path.isdir("/root/reference"),
                    reason="reference mount not available")
def test_fedlabels_vat_label_selection_matches_reference():
    """Semisupervision cross-check, selection half (review round 3 missing
    item: FedLabels never compared against the real reference).  The
    pseudo-label selector is the reference's ``get_label_VAT``
    (``utils/utils.py:620-680``, comp='var'): per-sample variance
    contest between the round-initial ("local") and sup-trained
    ("server") probability rows, argmax label of the winner iff its max
    prob clears ``thre``, confidence weight = loser-variance /
    winner-variance.  Full-trajectory parity is out of scope BY
    STRUCTURE (the experiment model is a BatchNorm ResNet, same block
    as the resnet family) — so run the ACTUAL reference function on
    synthetic probability rows and demand our mask-based in-jit
    equivalents (``strategies/fedlabels.py::_unsup_train``) agree
    per-sample on selection, label, and weight."""
    import numpy as np
    torch = pytest.importorskip("torch")
    from importlib.machinery import SourceFileLoader

    sys.path.insert(0, "/root/reference")
    sys.path.insert(0, os.path.join(REPO, "tools", "ref_shims"))
    try:
        ref_utils = SourceFileLoader(
            "ref_utils_fedlabels",
            "/root/reference/utils/utils.py").load_module()
    finally:
        sys.path.pop(0), sys.path.pop(0)

    rng = np.random.default_rng(7)
    B, C = 64, 5
    # softmaxed rows like the trainer feeds (temp applied upstream)
    def probs():
        z = rng.normal(size=(B, C)) * 2.0
        e = np.exp(z - z.max(axis=1, keepdims=True))
        return (e / e.sum(axis=1, keepdims=True)).astype(np.float32)
    local, server = probs(), probs()
    thre = 0.45

    labels, idx, var, ratio = ref_utils.get_label_VAT(
        torch.from_numpy(local), torch.from_numpy(server), thre, "var")

    # our mask math (strategies/fedlabels.py::_unsup_train step body)
    import jax.numpy as jnp
    lvar = jnp.var(jnp.asarray(local), axis=-1)
    svar = jnp.var(jnp.asarray(server), axis=-1)
    use_local = lvar >= svar
    chosen = jnp.where(use_local[:, None], jnp.asarray(local),
                       jnp.asarray(server))
    est_mask = (jnp.max(chosen, axis=-1) > thre)
    est_labels = jnp.argmax(chosen, axis=-1)
    est_var = jnp.where(use_local, svar / jnp.maximum(lvar, 1e-12),
                        lvar / jnp.maximum(svar, 1e-12))

    sel = np.flatnonzero(np.asarray(est_mask))
    assert sel.tolist() == list(idx)          # same samples selected
    np.testing.assert_array_equal(
        np.asarray(est_labels)[sel], np.asarray(torch.stack(list(labels))))
    np.testing.assert_allclose(
        np.asarray(est_var)[sel], np.asarray(torch.stack(list(var))),
        rtol=1e-5, atol=1e-6)
    # both sides must actually have been exercised (local and server wins)
    assert 0.0 < float(ratio) < 1.0


@pytest.mark.skipif(not os.path.isdir("/root/reference"),
                    reason="reference mount not available")
def test_fedlabels_combine_matches_reference():
    """Semisupervision cross-check, aggregation half: run the ACTUAL
    reference ``FedLabels.combine_payloads``
    (``core/strategies/fedlabels.py:120-216``) on synthetic dual
    payloads for a tiny torch Linear — sup halves averaged UNIFORMLY
    (ratio 1/K), unsup halves sample-weighted (n_k/sum), model loaded as
    sup/2 + unsup/2 — and demand our ``combine_parts`` + SGD(lr=1)
    server step lands on identical weights from the same inputs."""
    import numpy as np
    torch = pytest.importorskip("torch")
    from importlib.machinery import SourceFileLoader

    sys.path.insert(0, "/root/reference")
    sys.path.insert(0, os.path.join(REPO, "tools", "ref_shims"))
    try:
        ref_fl = SourceFileLoader(
            "ref_fedlabels",
            "/root/reference/core/strategies/fedlabels.py").load_module()
    finally:
        sys.path.pop(0), sys.path.pop(0)

    torch.manual_seed(0)
    model = torch.nn.Linear(4, 3)
    rng = np.random.default_rng(3)
    K, weights = 3, [5.0, 2.0, 9.0]
    sup = [[rng.normal(size=(3, 4)).astype(np.float32),
            rng.normal(size=(3,)).astype(np.float32)] for _ in range(K)]
    unsup = [[rng.normal(size=(3, 4)).astype(np.float32),
              rng.normal(size=(3,)).astype(np.float32)] for _ in range(K)]

    cfg = {"model_config": {}, "client_config": {},
           "server_config": {}, "dp_config": None}
    strat = ref_fl.FedLabels(mode="server", config=cfg)

    class _Trainer:
        def __init__(self, m):
            self.model = m

        def update_model(self):
            pass

        def run_lr_scheduler(self, force_run_val=False):
            return None

    trainer = _Trainer(model)
    for w, s, u in zip(weights, sup, unsup):
        ok = strat.process_individual_payload(
            trainer, {"weight": w,
                      "gradients": [torch.from_numpy(t) for t in s]
                      + [torch.from_numpy(t) for t in u]})
        assert ok
    strat.combine_payloads(trainer, curr_iter=0,
                           num_clients_curr_iter=K, total_clients=K,
                           client_stats=None)
    ref_w = {k: np.asarray(v.detach())
             for k, v in model.state_dict().items()}

    # our side: engine part accumulation (round.py wsum) + combine_parts
    import jax.numpy as jnp

    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.strategies.fedlabels import FedLabels as OurFL
    ours = OurFL(FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 3,
                         "input_dim": 4},
        "strategy": "fedlabels",
        "server_config": {
            "max_iteration": 1, "num_clients_per_iteration": 3,
            "initial_lr_client": 1.0,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 100, "initial_val": False,
            "data_config": {"val": {"batch_size": 8}},
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "data_config": {"train": {"batch_size": 4}},
        },
    }))
    def wsum(ws, trees):
        return {
            "weight": sum(w * jnp.asarray(t[0]) for w, t in zip(ws, trees)),
            "bias": sum(w * jnp.asarray(t[1]) for w, t in zip(ws, trees)),
        }
    part_sums = {
        "sup": {"grad_sum": wsum([1.0] * K, sup),
                "weight_sum": jnp.asarray(float(K))},
        "unsup": {"grad_sum": wsum(weights, unsup),
                  "weight_sum": jnp.asarray(sum(weights))},
    }
    w0 = {"weight": jnp.zeros((3, 4)), "bias": jnp.zeros((3,))}
    agg, _ = ours.combine_parts(part_sums, None, None, None, K,
                                global_params=w0)
    final = {k: np.asarray(w0[k] - agg[k]) for k in w0}  # sgd lr=1

    np.testing.assert_allclose(final["weight"], ref_w["weight"],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(final["bias"], ref_w["bias"],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.skipif(not os.path.isdir("/root/reference"),
                    reason="reference mount not available")
def test_ecg_transplant_forward_exact():
    """ECG family cross-check (review round 3 missing item 2): compose the
    REFERENCE's own building blocks (``experiments/ecg_cnn/model.py`` —
    ConvNormPool x2, LSTM-over-channels, [h;c] attention mix, adaptive
    max-pool, fc) with ``norm_type='group'`` actually honored (the
    shipped ``Net`` hardcodes the BatchNorm default and never threads
    the option through — same config-ignoring quirk as the resnet
    family), transplant the weights into our flax ``_ECGNet`` and
    demand identical class probabilities.  Full-trajectory parity is
    out of scope BY STRUCTURE for the shipped net (BatchNorm running
    stats; docs/reference_quirks.md); this pins every other piece of
    the architecture cross-framework — conv/pad/pool arithmetic, the
    channels-as-time LSTM, the attention contraction, and the
    double-softmax divergence (we compare our softmax(logits) against
    their softmaxed forward output)."""
    import numpy as np
    torch = pytest.importorskip("torch")
    from importlib.machinery import SourceFileLoader
    from torch import nn as tnn

    sys.path.insert(0, "/root/reference")
    sys.path.insert(0, os.path.join(REPO, "tools", "ref_shims"))
    try:
        mod = SourceFileLoader(
            "ref_ecg_model",
            "/root/reference/experiments/ecg_cnn/model.py").load_module()
    finally:
        sys.path.pop(0), sys.path.pop(0)

    torch.manual_seed(0)
    H, C, L = 64, 5, 187
    conv1 = mod.ConvNormPool(1, H, 5, norm_type="group")
    conv2 = mod.ConvNormPool(H, H, 5, norm_type="group")
    rnn = mod.RNN(input_size=46, hid_size=H)
    attn = tnn.Linear(H, H, bias=False)
    fc = tnn.Linear(H, C)
    for m in (conv1, conv2, rnn, attn, fc):
        m.eval()

    def ref_fwd(x):  # x [B, 1, L] — Net.forward with GN blocks
        x = conv1(x)
        x = conv2(x)
        x_out, hid = rnn(x)
        x = torch.cat([hid[0], hid[1]], dim=0).transpose(0, 1)
        xa = torch.tanh(attn(x))
        x = xa.bmm(x_out)
        x = x.transpose(2, 1)
        x = torch.nn.functional.adaptive_max_pool1d(x, 1)
        x = x.view(-1, x.size(1))
        return torch.softmax(fc(x), dim=-1)

    import jax
    import jax.numpy as jnp

    from msrflute_tpu.config import ModelConfig
    from msrflute_tpu.models import make_task
    task = make_task(ModelConfig(model_type="ECG_CNN",
                                 extra={"num_classes": C, "num_frames": L}))
    params = jax.device_get(task.init_params(jax.random.PRNGKey(0)))

    def conv_w(w):  # torch conv1d [O, I, k] -> flax [k, I, O]
        return np.asarray(w.detach()).transpose(2, 1, 0)

    def fill_cnp(dst, src):
        for j, tname in enumerate(("conv_1", "conv_2", "conv_3")):
            tc = getattr(src, tname)
            dst[f"Conv_{j}"]["kernel"] = conv_w(tc.weight)
            dst[f"Conv_{j}"]["bias"] = np.asarray(tc.bias.detach())
            tg = getattr(src, f"normalization_{j + 1}")
            dst[f"GroupNorm_{j}"]["scale"] = np.asarray(tg.weight.detach())
            dst[f"GroupNorm_{j}"]["bias"] = np.asarray(tg.bias.detach())

    fill_cnp(params["_ConvNormPool_0"], conv1)
    fill_cnp(params["_ConvNormPool_1"], conv2)
    lstm = rnn.rnn_layer
    cell = params["OptimizedLSTMCell_0"]
    w_ih = np.asarray(lstm.weight_ih_l0.detach())
    w_hh = np.asarray(lstm.weight_hh_l0.detach())
    b = (np.asarray(lstm.bias_ih_l0.detach())
         + np.asarray(lstm.bias_hh_l0.detach()))
    for k, g in enumerate("ifgo"):
        sl = slice(k * H, (k + 1) * H)
        cell[f"i{g}"]["kernel"] = w_ih[sl].T
        cell[f"h{g}"]["kernel"] = w_hh[sl].T
        cell[f"h{g}"]["bias"] = b[sl]
    params["Dense_0"]["kernel"] = np.asarray(attn.weight.detach()).T
    params["Dense_1"]["kernel"] = np.asarray(fc.weight.detach()).T
    params["Dense_1"]["bias"] = np.asarray(fc.bias.detach())

    x = np.random.default_rng(1).normal(size=(3, L)).astype(np.float32)
    with torch.no_grad():
        ref_p = np.asarray(ref_fwd(torch.from_numpy(x)[:, None, :]))
    ours_p = np.asarray(jax.nn.softmax(
        task.apply(params, jnp.asarray(x)), axis=-1))
    np.testing.assert_allclose(ours_p, ref_p, rtol=1e-5, atol=1e-6)


@pytest.mark.skipif(not os.path.isdir("/root/reference"),
                    reason="reference mount not available")
def test_fednewsrec_transplant_forward_exact():
    """FedNewsRec family cross-check (review round 3 missing item 2, the
    last family with zero cross-framework evidence): instantiate the
    REFERENCE's actual ``FedNewsRec`` torch net
    (``experiments/fednewsrec/fednewsrec_model.py:316-360``) with a
    synthetic frozen word table (the glove file is unfetchable —
    zero egress), transplant every weight into our ``arch:
    "fednewsrec"`` faithful flax variant, and demand identical
    candidate scores: conv phase, projection-less multi-head
    attention, tanh attentive pooling, and the dual-path user encoder
    (tail-20 GRU last-step + attention pool, stacked and pooled)."""
    import numpy as np
    torch = pytest.importorskip("torch")
    from importlib.machinery import SourceFileLoader

    sys.path.insert(0, "/root/reference")
    sys.path.insert(0, os.path.join(REPO, "tools", "ref_shims"))
    try:
        mod = SourceFileLoader(
            "ref_fednewsrec_model",
            "/root/reference/experiments/fednewsrec/fednewsrec_model.py"
        ).load_module()
    finally:
        sys.path.pop(0), sys.path.pop(0)

    V, E, HIST, L, C = 200, 300, 50, 30, 5
    rng = np.random.default_rng(0)
    emb = rng.normal(scale=0.1, size=(V, E)).astype(np.float32)
    # the reference net is cuda-hardwired in TimeDistributed
    # (torch.tensor([]).cuda(...)); bypass it by calling doc/user
    # encoders the way forward() composes them, on CPU
    torch.manual_seed(0)
    net = mod.FedNewsRec(emb)
    net.eval()
    clicked = rng.integers(0, V, size=(2, HIST, L))
    cands = rng.integers(0, V, size=(2, C, L))
    with torch.no_grad():
        cw = net.title_word_embedding_layer(torch.tensor(clicked))
        aw = net.title_word_embedding_layer(torch.tensor(cands))
        click_vecs = torch.stack(
            [net.doc_encoder(cw[:, i]) for i in range(HIST)], dim=1)
        cand_vecs = torch.stack(
            [net.doc_encoder(aw[:, i]) for i in range(C)], dim=1)
        user_vec = net.user_encoder(click_vecs)
        ref_scores = np.asarray(
            torch.einsum("ijk,ik->ij", cand_vecs, user_vec))

    import jax
    import jax.numpy as jnp

    from msrflute_tpu.config import ModelConfig
    from msrflute_tpu.models import make_task
    task = make_task(ModelConfig(model_type="FEDNEWSREC", extra={
        "arch": "fednewsrec", "vocab_size": V, "embed_dim": E,
        "max_title_length": L, "max_history": HIST, "npratio": C - 1,
        "embedding_matrix": emb}))
    params = jax.device_get(task.init_params(jax.random.PRNGKey(0)))

    def lin(w):
        return np.asarray(w.detach()).T

    def fill_attn(dst, src):
        dst["WQ"]["kernel"] = lin(src.WQ.weight)
        dst["WK"]["kernel"] = lin(src.WK.weight)
        dst["WV"]["kernel"] = lin(src.WV.weight)

    def fill_pool(dst, src):
        dst["Dense_0"]["kernel"] = lin(src.dense.weight)
        dst["Dense_0"]["bias"] = np.asarray(src.dense.bias.detach())
        dst["Dense_1"]["kernel"] = lin(src.dense2.weight)
        dst["Dense_1"]["bias"] = np.asarray(src.dense2.bias.detach())

    de, ue = net.doc_encoder, net.user_encoder
    pd = params["_RefDocEncoder_0"]
    tconv = de.phase1[2]  # Dropout, Swap, Conv1d, ReLU, Dropout, Swap
    pd["conv"]["kernel"] = np.asarray(
        tconv.weight.detach()).transpose(2, 1, 0)
    pd["conv"]["bias"] = np.asarray(tconv.bias.detach())
    fill_attn(pd["_RefAttention_0"], de.attention)
    fill_pool(pd["_AttentivePooling_0"], de.phase2[2])

    pu = params["_RefUserEncoder_0"]
    fill_attn(pu["_RefAttention_0"], ue.attention2)
    fill_pool(pu["_AttentivePooling_0"], ue.pool2)
    fill_pool(pu["_AttentivePooling_1"], ue.pool3)
    H = 400
    gru = ue.gru2
    w_ih = np.asarray(gru.weight_ih_l0.detach())   # gates r, z, n
    w_hh = np.asarray(gru.weight_hh_l0.detach())
    b_ih = np.asarray(gru.bias_ih_l0.detach())
    b_hh = np.asarray(gru.bias_hh_l0.detach())
    cell = pu["GRUCell_0"]
    for k, g in enumerate("rzn"):
        sl = slice(k * H, (k + 1) * H)
        cell[f"i{g}" if g != "n" else "in"]["kernel"] = w_ih[sl].T
        cell[f"h{g}" if g != "n" else "hn"]["kernel"] = w_hh[sl].T
    # flax: r/z fold both torch biases into the i-side bias; the n gate
    # keeps them split (hn bias sits inside the r* gate product)
    cell["ir"]["bias"] = b_ih[0 * H:1 * H] + b_hh[0 * H:1 * H]
    cell["iz"]["bias"] = b_ih[1 * H:2 * H] + b_hh[1 * H:2 * H]
    cell["in"]["bias"] = b_ih[2 * H:3 * H]
    cell["hn"]["bias"] = b_hh[2 * H:3 * H]

    batch = {"clicked": jnp.asarray(clicked, jnp.int32),
             "cands": jnp.asarray(cands, jnp.int32)}
    ours = np.asarray(task._scores(params, batch))
    np.testing.assert_allclose(ours, ref_scores, rtol=1e-4, atol=1e-4)
