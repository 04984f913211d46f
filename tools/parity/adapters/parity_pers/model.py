"""Personalization parity adapter (review round 3 item 5).

Subclasses the reference's own LR task class
(``experiments/cv_lr_mnist/model.py:23``) with two additions the
personalization flow needs:

- the constructor loads ``pretrained_model_path`` itself: the reference's
  per-user LOCAL models are built by bare ``make_model``
  (``core/client.py:390`` + ``experiments/__init__.py:19``) which draws a
  fresh torch-RNG init — unreproducible cross-framework; loading the seed
  file here pins both sides' local cold-start to the same weights (our
  side: ``personalization_init: initial``);
- ``inference`` returns the dict-output contract the personalized eval
  requires (``convex_inference`` mixes ``output['probabilities']``,
  ``utils/utils.py:598-603``), mirroring the cv experiment's model
  (``experiments/cv/model.py:288-303``: LOG-softmax under the
  'probabilities' key).
"""
import numpy as np
import torch
from experiments.cv_lr_mnist.model import LR as _LR


class LR(_LR):
    def __init__(self, model_config):
        super().__init__(model_config)
        path = model_config.get("pretrained_model_path")
        if path:
            self.load_state_dict(torch.load(path))

    def inference(self, input):
        features, labels = input["x"], input["y"]
        output = self.net(features)
        logp = torch.nn.LogSoftmax(dim=1)(output)
        acc = torch.mean(
            (torch.argmax(output, dim=1) == labels).float()).item()
        n = features.shape[0]
        return {"output": {"probabilities": logp.detach().numpy(),
                           "predictions": np.arange(n),
                           "labels": labels.numpy()},
                "acc": acc, "batch_size": n}
