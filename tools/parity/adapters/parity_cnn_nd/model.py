"""Dropout-free variant of the CNN parity adapter (review round 3 item 3).

Subclasses the reference's own CNN task class
(``experiments/cv_cnn_femnist/model.py:82``, net = FedML ``CNN_DropOut``)
and zeroes both dropout probabilities — ``torch.nn.Dropout(p=0)`` is the
identity, so the forward pass becomes fully deterministic and the
cross-framework comparison upgrades from endpoint-grade to
trajectory-exact.  The harness runs it with ``-task parity_cnn`` for
data loading; only ``model_folder`` points here.
"""
from experiments.parity_cnn.model import CNN as _CNN


class CNN(_CNN):
    def __init__(self, model_config):
        super().__init__(model_config)
        self.net.dropout_1.p = 0.0
        self.net.dropout_2.p = 0.0
