"""Personalization — per-user local models with convex interpolation.

Parity target: reference personalization flow
(``experiments/cv/server.py``, ``core/client.py:387-443``,
``utils/utils.py:598-617``):

- every user owns a persistent *local* model and a scalar ``alpha``;
- when sampled, the user trains BOTH the global model (the normal federated
  path) and its local model on the same data;
- ``alpha`` takes one SGD step on the interpolation objective:
  ``grad_alpha = sum((w_g - w_p) . (alpha*pg_g + (1-alpha)*pg_p)) + 0.02*alpha``
  with ``alpha`` clipped to [1e-4, 0.9999] (``utils/utils.py:607-617``,
  the reference's argument names are swapped — semantics preserved);
- evaluation interpolates logits: ``alpha*personal + (1-alpha)*global``
  (``convex_inference``, ``utils/utils.py:600-605``), metric = accuracy.

TPU-native: local models of the round's sampled users are stacked on the
clients axis and trained by the SAME vmapped client-update program as the
global pass — one extra shard_map program per round, no per-user Python.
Per-user state lives host-side in :class:`PersonalizationStore` between
rounds (the analogue of the reference's ``<user>_model.tar`` /
``<user>_alpha`` files) and is checkpointed with msgpack.

Divergence (configurable): the reference cold-starts a user's local model
with random init (``make_model``, ``core/client.py:390``); default here is
to clone the current global params (``personalization_init: global``), which
dominates random init; set ``personalization_init: random`` for the
reference behavior.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization
from jax.sharding import NamedSharding, PartitionSpec as P

from ..data.batching import pack_round_batches
from ..parallel.mesh import CLIENTS_AXIS, pad_to_mesh
from ..utils.logging import log_metric, print_rank
from ..utils.metrics import Metric
from .server import OptimizationServer


class PersonalizationStore:
    """Host-side per-user (local_params, alpha) state.

    Persistence mirrors the reference's per-user files
    (``<user>_model.tar`` / ``<user>_alpha``, ``core/client.py:408-443``):
    one msgpack per user, written only when that user was updated — so a
    round's save cost is O(sampled users), not O(all seen users).
    """

    def __init__(self, init_alpha: float, store_dir: Optional[str] = None):
        self.init_alpha = float(init_alpha)
        self.store_dir = store_dir
        self.params: Dict[int, Any] = {}
        self.alpha: Dict[int, float] = {}
        self._dirty: set = set()

    def get(self, user_idx: int, default_params) -> Tuple[Any, float]:
        return (self.params.get(user_idx, default_params),
                self.alpha.get(user_idx, self.init_alpha))

    def put(self, user_idx: int, params: Any, alpha: float) -> None:
        self.params[user_idx] = params
        self.alpha[user_idx] = float(alpha)
        self._dirty.add(user_idx)

    def _user_path(self, uid: int) -> str:
        return os.path.join(self.store_dir, f"user{uid}_model.msgpack")

    def save(self) -> None:
        """Flush users updated since the last save."""
        if self.store_dir is None:
            return
        os.makedirs(self.store_dir, exist_ok=True)
        for uid in self._dirty:
            blob = serialization.msgpack_serialize(serialization.to_state_dict(
                {"alpha": self.alpha[uid],
                 "params": jax.device_get(self.params[uid])}))
            with open(self._user_path(uid), "wb") as fh:
                fh.write(blob)
        self._dirty.clear()

    def load(self, template) -> bool:
        if self.store_dir is None or not os.path.isdir(self.store_dir):
            return False
        tmpl = serialization.to_state_dict(jax.device_get(template))
        found = False
        for name in os.listdir(self.store_dir):
            if not (name.startswith("user") and name.endswith("_model.msgpack")):
                continue
            uid = int(name[len("user"):-len("_model.msgpack")])
            with open(os.path.join(self.store_dir, name), "rb") as fh:
                raw = serialization.msgpack_restore(fh.read())
            self.alpha[uid] = float(raw["alpha"])
            self.params[uid] = serialization.from_state_dict(
                tmpl, raw["params"])
            found = True
        return found


class PersonalizationServer(OptimizationServer):
    """OptimizationServer + per-user personalization passes.

    Two modes: the host path (default) runs a separate jitted personal
    pass per round inside the ``_sample`` hook and keeps per-user state
    in a host-side :class:`PersonalizationStore`; with
    ``server_config.fused_carry: true`` the per-user local models and
    alphas instead ride ``strategy_state`` as device-resident carry
    (``strategies/personalized.py``) — the round pipelines like FedAvg,
    durability rides the model checkpoint, and the personalized eval
    reads the tables back with one explicit fetch at eval boundaries.
    """

    #: under fused_carry the ``_sample`` hook degrades to the base
    #: sampler (the personal pass moved into the round program), so the
    #: server's host-orchestrated predicate must not count it
    fused_carry_sample = True

    def _select_strategy(self, config) -> type:
        if self._fused_carry:
            from ..strategies.personalized import PersonalizedFedAvg
            strat = (config.strategy or "fedavg").lower()
            if strat not in ("fedavg", "fedprox"):
                raise ValueError(
                    f"fused_carry personalization composes only with "
                    f"strategy: fedavg/fedprox (got {strat!r}) — the "
                    "carry tables replace the host store, and other "
                    "strategies keep their own state; drop fused_carry")
            return PersonalizedFedAvg
        return super()._select_strategy(config)

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        cc = self.config.client_config
        self.alpha0 = float(cc.get("convex_model_interp", 0.75))
        if self._fused_carry:
            # device-carry mode: per-user state lives in strategy_state
            # (checkpointed with the model), the personal pass runs
            # inside the fused round program, and there is no host store
            self.store = None
            self._personal_fn = None
            self._personal_eval_fn = None
            self._interp_space = self.config.server_config.get(
                "personalization_interp", "probs")
            return
        self._store_path = os.path.join(self.ckpt.model_dir,
                                        "personalization")
        self.store = PersonalizationStore(self.alpha0, self._store_path)
        if self.config.server_config.get("resume_from_checkpoint", False):
            if self.store.load(self.state.params):
                print_rank(f"restored personalization state for "
                           f"{len(self.store.alpha)} users")
        self._personal_fn = None
        self._personal_eval_fn = None
        init_kind = self.config.server_config.get(
            "personalization_init", "global")
        self._random_init = init_kind == "random"
        # "initial": cold-start local models from the ROUND-0 global
        # weights.  With a pretrained_model_path this is exactly what a
        # reference adapter that loads the seed file in its constructor
        # sees (the reference's own make_model draws a fresh torch-RNG
        # init, core/client.py:390 + experiments/__init__.py:19 — which no
        # cross-framework run can reproduce; the parity harness pins both
        # sides to the seed file instead)
        self._initial_params = (jax.device_get(self.state.params)
                                if init_kind == "initial" else None)
        # interpolation space for the personalized eval: the reference
        # interpolates LOG-probabilities (cv model.py:294 applies
        # LogSoftmax, convex_inference mixes those — a geometric prob
        # mean), while plain "probs" (arithmetic mean, the standard
        # ensemble) is our default; argmax differs near ties, so parity
        # runs set personalization_interp: logprobs
        self._interp_space = self.config.server_config.get(
            "personalization_interp", "probs")
        # the personal pass reads the CURRENT global params per round, so
        # round fusion would train local models against stale globals
        if int(self.config.server_config.get("rounds_per_step", 1) or 1) > 1:
            print_rank("personalization forces rounds_per_step=1")
            # item assignment, NOT setattr: rounds_per_step is an extras
            # key, and a plain attribute would be invisible to .get()
            self.config.server_config["rounds_per_step"] = 1

    def _round_housekeeping(self, round_no, val_freq, rec_freq,
                            skip_latest=False, rng_snapshot=None,
                            chunk=None):
        super()._round_housekeeping(round_no, val_freq, rec_freq,
                                    skip_latest=skip_latest,
                                    rng_snapshot=rng_snapshot, chunk=chunk)
        # personalized eval: convex logit interpolation over users with
        # local state (reference convex_inference during run_testvalidate,
        # core/client.py:167-183)
        if round_no % val_freq == 0 and self.val_dataset is not None:
            self.personalized_accuracy(self.val_dataset)
        # persist ONLY the users updated this round (reference writes
        # <user>_model.tar per processed client, core/client.py:408-443);
        # fused mode has no host store — durability rides the model
        # checkpoint, whose strategy_state IS the personalization state
        if self.store is not None:
            self.store.save()

    # -- jitted per-user local pass ------------------------------------
    def _build_personal_fn(self):
        engine = self.engine
        client_update = engine.client_update
        cspec = P(CLIENTS_AXIS)
        rspec = P()
        from jax import shard_map

        def shard_body(global_params, local_params, alphas, arrays,
                       sample_mask, client_mask, client_ids, client_lr, rng):
            def per_user(lp, alpha, arr, mask, cm, cid):
                rng_c = jax.random.fold_in(rng, cid + 104729)
                # global-model pass pseudo-grad (recomputed here so the
                # alpha update sees both pseudo-gradients, as in the
                # reference where both trainers run in the same round)
                pg_g, _, _, _ = client_update(global_params, arr, mask,
                                              client_lr, rng_c)
                # local-model pass
                pg_p, tl_p, ns, _ = client_update(lp, arr, mask, client_lr,
                                                  jax.random.fold_in(rng_c, 5))
                new_lp = jax.tree.map(lambda w, g: w - g, lp, pg_p)
                # alpha SGD step (utils/utils.py:607-617); the reference
                # calls alpha_update after BOTH trainings, so the dot uses
                # post-training params: (w_g - pg_g) - (lp - pg_p)
                dots = jax.tree.map(
                    lambda wg, wp, gg, gp: jnp.sum(
                        ((wg - gg) - (wp - gp)) *
                        (alpha * gg + (1.0 - alpha) * gp)),
                    global_params, lp, pg_g, pg_p)
                grad_alpha = sum(jax.tree.leaves(dots)) + 0.02 * alpha
                new_alpha = jnp.clip(alpha - client_lr * grad_alpha,
                                     1e-4, 0.9999)
                new_alpha = jnp.where(jnp.isfinite(new_alpha), new_alpha,
                                      jnp.asarray(self.alpha0))
                new_alpha = jnp.where(cm > 0, new_alpha, alpha)
                new_lp = jax.tree.map(
                    lambda new, old: jnp.where(cm > 0, new, old), new_lp, lp)
                return new_lp, new_alpha, tl_p * cm

            return jax.vmap(per_user)(local_params, alphas, arrays,
                                      sample_mask, client_mask, client_ids)

        fn = shard_map(
            shard_body, mesh=engine.mesh,
            in_specs=(rspec, cspec, cspec, cspec, cspec, cspec, cspec,
                      rspec, rspec),
            out_specs=cspec, check_vma=False)
        return jax.jit(fn, donate_argnums=(1,))

    # -- hook into the round loop --------------------------------------
    def train(self):
        state = super().train()
        if self.store is not None:
            self.store.save()
        return state

    def _sample(self):
        sampled = super()._sample()
        if self.store is not None:
            # host path only: fused_carry runs the personal pass inside
            # the round program (strategies/personalized.py), so sampling
            # degrades to the base sampler and the pipeline stays eligible
            self._run_personal_pass(sampled)
        return sampled

    def _stage_on_clients_axis(self, host_params_list, alphas, batch):
        """Stack per-user param pytrees + stage a packed round batch onto
        the clients mesh axis (shared by the round pass and the eval)."""
        sharding = NamedSharding(self.mesh, P(CLIENTS_AXIS))
        stage = lambda v: jax.device_put(v, sharding)
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *host_params_list)
        return (jax.tree.map(stage, stacked),
                stage(np.asarray(alphas, np.float32)),
                {k: stage(v) for k, v in batch.arrays.items()},
                stage(batch.sample_mask), stage(batch.client_mask), stage)

    def _run_personal_pass(self, sampled) -> None:
        """Train sampled users' local models + alphas for this round."""
        if self._personal_fn is None:
            self._personal_fn = self._build_personal_fn()
        batch = pack_round_batches(
            self.train_dataset, sampled, self.batch_size, self.max_steps,
            rng=self._np_rng, pad_clients_to=pad_to_mesh(len(sampled), self.mesh),
            desired_max_samples=self.desired_max_samples)
        k_pad = batch.client_mask.shape[0]
        if self._random_init:
            default = self._random_params()
        elif self._initial_params is not None:
            default = self._initial_params
        else:
            default = jax.device_get(self.state.params)
        locals_, alphas = [], []
        for j in range(k_pad):
            cid = int(batch.client_ids[j])
            lp, a = self.store.get(cid if cid >= 0 else -1, default)
            locals_.append(lp)
            alphas.append(a)
        lps_dev, alphas_dev, arrays_dev, smask, cmask, stage = \
            self._stage_on_clients_axis(locals_, alphas, batch)
        rng = self._next_rng()
        new_lp, new_alpha, tl = self._personal_fn(
            self.state.params, lps_dev, alphas_dev, arrays_dev, smask, cmask,
            stage(batch.client_ids),
            jnp.asarray(self.initial_lr_client * self.lr_weight, jnp.float32),
            rng)
        # one bundled fetch (two separate device_gets paid two transfers)
        new_lp, new_alpha = jax.device_get((new_lp, new_alpha))
        for j in range(k_pad):
            cid = int(batch.client_ids[j])
            if cid < 0:
                continue
            self.store.put(cid, jax.tree.map(lambda x: x[j], new_lp),
                           float(new_alpha[j]))

    def _random_params(self):
        sub = self._next_rng()
        return jax.device_get(self.task.init_params(sub))

    # -- personalized eval ---------------------------------------------
    def _build_personal_eval_fn(self):
        """One jitted shard_map+vmap program scoring ALL users' convex-
        interpolated logits (reference ``convex_inference``,
        ``utils/utils.py:600-605``) — users ride the clients mesh axis with
        their local params stacked, exactly like the round path."""
        task = self.task
        from jax import shard_map
        cspec = P(CLIENTS_AXIS)
        rspec = P()

        logspace = self._interp_space == "logprobs"

        def shard_body(gp, lps, alphas, arrays, sample_mask, client_mask):
            def per_user(lp, alpha, arr, mask, cm):
                x = arr["x"].reshape((-1,) + arr["x"].shape[2:])
                y = arr["y"].reshape(-1).astype(jnp.int32)
                m = mask.reshape(-1) * cm
                squash = jax.nn.log_softmax if logspace else jax.nn.softmax
                probs = (alpha * squash(task.apply(lp, x)) +
                         (1.0 - alpha) * squash(task.apply(gp, x)))
                pred = jnp.argmax(probs, axis=-1)
                # per-user loss = (global CE + local CE) / 2, sample-
                # weighted across users — the reference's personalized
                # "Val loss" definition (core/client.py:218-219: plain
                # average of the two models' losses; alpha plays no role)
                flat = {"x": x, "y": y, "sample_mask": m}
                lg = task.loss(gp, flat, None, False)[0]
                ll = task.loss(lp, flat, None, False)[0]
                n = jnp.sum(m)
                return (jnp.sum((pred == y).astype(jnp.float32) * m),
                        jnp.sum(m),
                        0.5 * (lg + ll) * n * (cm > 0))

            c, t, ls = jax.vmap(per_user)(lps, alphas, arrays, sample_mask,
                                          client_mask)
            return (jax.lax.psum(jnp.sum(c), CLIENTS_AXIS),
                    jax.lax.psum(jnp.sum(t), CLIENTS_AXIS),
                    jax.lax.psum(jnp.sum(ls), CLIENTS_AXIS))

        fn = shard_map(shard_body, mesh=self.engine.mesh,
                       in_specs=(rspec, cspec, cspec, cspec, cspec, cspec),
                       out_specs=(rspec, rspec, rspec), check_vma=False)
        return jax.jit(fn)

    def personalized_accuracy(self, dataset) -> Optional[float]:
        """Back-compat wrapper: accuracy component of the personalized
        eval."""
        res = self.personalized_eval(dataset)
        return None if res is None else res[0]

    def personalized_eval(self, dataset) -> Optional[Tuple[float, float]]:
        """Convex-interpolated accuracy + reference-style personalized
        loss over ALL of the dataset's users — one compiled program
        services everyone.  Users without local state evaluate with the
        global model in both slots (interp of identical models == the
        global model; loss (g+g)/2 == g), exactly the reference's fallback
        when no ``<user>_model.tar`` exists (core/client.py:197-219).

        Chunk width is FIXED at the mesh's client-axis size: one local-model
        replica per device lane bounds the staging memory (K param copies is
        the real cost at ResNet scale), and the constant shape means exactly
        one compilation no matter how the store grows.  ``S`` respects the
        configured ``desired_max_samples`` cap when present."""
        if not hasattr(self.task, "apply"):
            return None
        if self.store is None and \
                getattr(self, "fleet_pager", None) is not None:
            # fleet paged carry: the device tables hold only the page
            # pool's resident slots, but eval boundaries fully drain
            # the pipeline ring, so the pager's HOST store holds every
            # participated user's current (local, alpha, seen) row —
            # zero device reads here at all
            pager = self.fleet_pager
            if not pager.has_rows():
                return None  # nothing personalized yet
            gp_host = jax.device_get(self.state.params)
            leaves, treedef = jax.tree.flatten(gp_host)
            spans = []
            off = 0
            for leaf in leaves:
                spans.append((off, int(np.prod(leaf.shape)), leaf.shape))
                off += spans[-1][1]

            def _unravel_np(vec):
                return jax.tree.unflatten(treedef, [
                    np.asarray(vec[o:o + n]).reshape(shp)
                    for o, n, shp in spans])

            def get_lp(u):
                row = pager.user_row(u)
                return (_unravel_np(row["local"])
                        if row is not None and float(row["seen"]) > 0
                        else gp_host)

            def get_alpha(u):
                row = pager.user_row(u)
                return (float(row["alpha"])
                        if row is not None and float(row["seen"]) > 0
                        else self.alpha0)
        elif self.store is None:
            # fused_carry: ONE explicit fetch of the carry tables at this
            # eval boundary (the sanctioned crossing — eval boundaries
            # already fetch; the per-round loop still pays exactly one
            # packed transfer).  Rows are unraveled host-side in
            # tree-flatten order, the exact inverse of the strategy's
            # ravel_pytree rows — no device round trip per user.  The
            # cheap ``seen`` gate crosses FIRST: when nothing is
            # personalized yet the early return must not have paid for
            # the [N, n_params] local table (or the model params).
            ss = self.state.strategy_state
            # flint: disable=host-sync deliberate split — the [N] seen gate crosses alone so the early return never pays for the [N, n_params] local table
            seen_tab = np.asarray(jax.device_get(ss["seen"]))
            if not bool(np.any(seen_tab > 0)):
                # nothing personalized yet (e.g. initial_val before
                # round 1) — the standard global eval covers this state
                return None
            gp_host = jax.device_get(self.state.params)
            local_tab, alpha_tab = jax.device_get(
                (ss["local"], ss["alpha"]))
            leaves, treedef = jax.tree.flatten(gp_host)
            spans = []
            off = 0
            for leaf in leaves:
                spans.append((off, int(np.prod(leaf.shape)), leaf.shape))
                off += spans[-1][1]

            def _unravel_np(vec):
                return jax.tree.unflatten(treedef, [
                    np.asarray(vec[o:o + n]).reshape(shp)
                    for o, n, shp in spans])

            def get_lp(u):
                return (_unravel_np(local_tab[u]) if u < len(seen_tab)
                        and seen_tab[u] > 0 else gp_host)

            def get_alpha(u):
                return (float(alpha_tab[u]) if u < len(seen_tab)
                        and seen_tab[u] > 0 else self.alpha0)
        else:
            if not self.store.alpha:
                # nothing personalized yet (e.g. initial_val before
                # round 1): the whole program would reduce to 4 redundant
                # global forwards per user — skip; the standard global
                # eval already covers this state
                return None
            gp_host = jax.device_get(self.state.params)
            get_lp = lambda u: self.store.params.get(u, gp_host)
            get_alpha = lambda u: self.store.alpha.get(u, self.alpha0)
        uids = list(range(len(dataset)))
        if not uids:
            return None
        if self._personal_eval_fn is None:
            self._personal_eval_fn = self._build_personal_eval_fn()
        from ..data.batching import steps_for
        bs = int(self.config.server_config.data_config.val.get(
            "batch_size", self.batch_size))
        S = steps_for(int(max(dataset.num_samples)), bs,
                      self.desired_max_samples)
        chunk_k = self.mesh.shape[CLIENTS_AXIS]
        correct = total = loss_sum = 0.0
        for i in range(0, len(uids), chunk_k):
            part = uids[i:i + chunk_k]
            batch = pack_round_batches(
                dataset, part, bs, S, shuffle=False, pad_clients_to=chunk_k,
                desired_max_samples=self.desired_max_samples)
            lps = [get_lp(u) for u in part]
            alphas = [get_alpha(u) for u in part]
            while len(lps) < chunk_k:  # mesh-padding lanes (client_mask 0)
                lps.append(gp_host)
                alphas.append(self.alpha0)
            lps_dev, alphas_dev, arrays_dev, smask, cmask, _ = \
                self._stage_on_clients_axis(lps, alphas, batch)
            c, t, ls = self._personal_eval_fn(
                self.state.params, lps_dev, alphas_dev, arrays_dev,
                smask, cmask)
            correct += float(c)
            total += float(t)
            loss_sum += float(ls)
        if total == 0:
            return None
        acc = correct / total
        loss = loss_sum / total
        log_metric("Personalized val acc", acc, step=self.state.round)
        log_metric("Personalized val loss", loss, step=self.state.round)
        return acc, loss
