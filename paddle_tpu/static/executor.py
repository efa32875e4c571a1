"""Static-graph Executor.

TPU-native replacement for the reference's C++ Executor hot loop
(/root/reference/paddle/fluid/framework/executor.cc:491 `op->Run` per op)
and the feed/fetch machinery (executor.cc:296-370): the whole Program
compiles into ONE jitted XLA callable keyed by (program version, feed
shapes, fetch set) — per-op interpretation, scope management and GC all
disappear into XLA. A python interpreter path (`_interpret`) exists as the
debug analogue of the reference's original op loop."""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from ..framework import state
from ..framework.place import Place
from ..framework.tensor import Tensor
from ..observability import tracing
from .program import Program, Variable, default_main_program

__all__ = ["Executor", "global_scope", "Scope"]


class Scope:
    """Name→value store for persistables (reference: framework/scope.h:62).
    Parameters live as the captured Tensors' arrays; this scope tracks them
    for find_var compatibility."""

    def __init__(self):
        self._vars = {}

    def find_var(self, name):
        return self._vars.get(name)

    def var(self, name):
        return self._vars.setdefault(name, None)


_global_scope = Scope()


def global_scope():
    return _global_scope


class _CompiledProgram:
    def __init__(self, program: Program, feed_names, fetch_names,
                 train: bool):
        from .program import prune_ops
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.train = train
        targets = set(fetch_names)
        if train:
            targets.add(program.optimize_directive[1].name)
        targets |= {name for _, name in program.buffer_updates}
        # fetching a pass-removed var goes through its alias: keep the
        # alias TARGET alive through the prune
        from .program import extend_targets_with_aliases
        extend_targets_with_aliases(targets, getattr(program, "aliases", {}))
        self.ops, needed = prune_ops(program.ops, targets)
        self.rng_names = [n for n in program.rng_inputs if n in needed]
        self.buffer_updates = [(b, n) for b, n in program.buffer_updates
                               if n in needed]
        cap_ids = list(program.captured)
        self.cap_tensors = [program.captured[i] for i in cap_ids]
        self.cap_names = [program.capture_names[i] for i in cap_ids]
        self.aliases = dict(getattr(program, "aliases", {}))
        if train:
            opt, loss_var = program.optimize_directive
            self.optimizer = opt
            self.loss_name = loss_var.name
            allow = (None if opt._parameter_list is None
                     else {id(p) for p in opt._parameter_list})
            self.params = [t for t in self.cap_tensors
                           if not t.stop_gradient
                           and getattr(t, "trainable", True)
                           and (allow is None or id(t) in allow)]
            # identity lookup (Tensor __eq__ is elementwise)
            self.param_idx = [next(i for i, t in enumerate(self.cap_tensors)
                                   if t is p) for p in self.params]
            # static split used every step: params ride the donated jit
            # argument, the rest stay un-donated captures
            self.rest_idx = [i for i in range(len(self.cap_tensors))
                             if i not in set(self.param_idx)]
            self.accs = [opt._get_accumulators(p) for p in self.params]
            # ASP (incubate/asp): params pruned with with_mask under a
            # decorated optimizer get their mask re-applied INSIDE the
            # compiled step — XLA fuses the multiply into the update.
            # The index set is static per compile; prune_model bumps
            # program.version so re-pruning recompiles.
            self.asp_idx = tuple(
                i for i, p in enumerate(self.params)
                if getattr(opt, "_asp_decorated", False)
                and getattr(p, "_asp_mask", None) is not None)
        from ..ops.pallas_kernels import pallas_selfcheck
        from ..jit import compile_cache
        compile_cache.configure()
        pallas_selfcheck()
        # train step: params (2) and accumulators (3) are donated — they
        # are replaced wholesale by run() after the call, so XLA may
        # update them in place instead of allocating fresh output buffers
        # (the eager engine's make_train_step donates the same way;
        # reference analogue: share_tensor_buffer_op_handle's in-place
        # reuse). Params are passed as their OWN argument, split out of
        # cap_arrays, so donation never aliases the non-donated captures.
        self._jitted = jax.jit(self._run) if not train else \
            jax.jit(self._run_train, donate_argnums=(2, 3))

    # -- pure interpreters ---------------------------------------------------
    def _forward_env(self, feed_arrays, cap_arrays, rng_arrays=()):
        env: Dict[str, object] = {}
        env.update(zip(self.feed_names, feed_arrays))
        env.update(zip(self.cap_names, cap_arrays))
        env.update(zip(self.rng_names, rng_arrays))
        for op in self.ops:
            ins = []
            for kind, ref in op.in_refs:
                if kind == "const":
                    ins.append(ref)
                elif ref not in env:
                    raise KeyError(
                        f"op {op.op_type} needs variable '{ref}' which is "
                        f"neither computed nor fed — missing from feed dict? "
                        f"(fed: {self.feed_names})")
                else:
                    ins.append(env[ref])
            outs = op.fn(*ins, **op.attrs)
            if not isinstance(outs, tuple):
                outs = (outs,)
            env.update(zip(op.out_names, outs))
        # vars removed by rewrite passes stay fetchable via their alias
        from .program import resolve_aliases_into_env
        return resolve_aliases_into_env(env, self.aliases)

    def _fetch(self, env):
        missing = [n for n in self.fetch_names if n not in env]
        if missing:
            raise KeyError(
                f"fetch target(s) {missing} not produced by this program "
                f"(known vars include feeds {self.feed_names} and op "
                f"outputs)")
        return [env[n] for n in self.fetch_names]

    def _run(self, feed_arrays, cap_arrays, rng_arrays):
        env = self._forward_env(feed_arrays, cap_arrays, rng_arrays)
        return self._fetch(env), [env[n] for _, n in self.buffer_updates]

    def _run_train(self, feed_arrays, cap_rest, param_arrays, acc_arrays,
                   t, lr, rng_arrays, mask_arrays=()):
        opt = self.optimizer

        def loss_of(param_arrays):
            caps = [None] * len(self.cap_tensors)
            for i, a in zip(self.param_idx, param_arrays):
                caps[i] = a
            for i, a in zip(self.rest_idx, cap_rest):
                caps[i] = a
            env = self._forward_env(feed_arrays, caps, rng_arrays)
            loss = env[self.loss_name]
            return loss.reshape(()), env

        params0 = list(param_arrays)
        (loss, env), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params0)

        gs = []
        for p, arr, g in zip(self.params, params0, grads):
            reg = getattr(p, "regularizer", None) or opt._regularization
            if reg is not None:
                g = reg(arr, g)
            gs.append(g)
        if opt._grad_clip is not None:
            pairs = list(zip(self.params, gs))
            gs = [g for _, g in opt._grad_clip(pairs)]

        new_params, new_accs = [], []
        acc_names = opt._accumulator_names
        for p, arr, g, acc in zip(self.params, params0, gs, acc_arrays):
            sargs = opt._per_param_static_args(p)
            rule = opt._rule_cls(p)._update_rule
            plr = lr * getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
            out = rule(sargs, arr, g, plr, t, *acc)
            new_params.append(out[0])
            new_accs.append(list(out[1:]))
        for k, i in enumerate(self.asp_idx):
            new_params[i] = new_params[i] * mask_arrays[k]
        fetches = self._fetch(env)
        buf_vals = [env[n] for _, n in self.buffer_updates]
        return fetches, new_params, new_accs, buf_vals

    # -- entry ---------------------------------------------------------------
    def run(self, feed_arrays):
        from ..framework.random import RNG
        # explicit device_put of host feeds rather than letting jit
        # transfer numpy implicitly (the transfer is then asynchronous)
        feed_arrays = [jax.device_put(a) if isinstance(a, np.ndarray) else a
                       for a in feed_arrays]
        cap_arrays = [t._data for t in self.cap_tensors]
        rng_arrays = [RNG.next_key() for _ in self.rng_names]
        if not self.train:
            fetches, buf_vals = self._jitted(feed_arrays, cap_arrays,
                                             rng_arrays)
            for (buf, _), v in zip(self.buffer_updates, buf_vals):
                buf._data = v
            return fetches
        opt = self.optimizer
        acc_names = opt._accumulator_names
        acc_arrays = [[a[n] for n in acc_names] for a in self.accs]
        opt._step_count += 1
        mask_arrays = tuple(self.params[i]._asp_mask for i in self.asp_idx)
        # split params out of the captures: they ride the donated argument
        # (the jit donates argnums 2/3) and must not also appear in the
        # non-donated cap_rest, or XLA would see aliased donated buffers
        cap_rest = [cap_arrays[i] for i in self.rest_idx]
        param_arrays = [cap_arrays[i] for i in self.param_idx]
        fetches, new_params, new_accs, buf_vals = self._jitted(
            feed_arrays, cap_rest, param_arrays, acc_arrays,
            np.int32(opt._step_count), np.float32(opt.get_lr()), rng_arrays,
            mask_arrays)
        for p, a in zip(self.params, new_params):
            p._data = a
        for acc, new in zip(self.accs, new_accs):
            for n, a in zip(acc_names, new):
                acc[n] = a
        for (buf, _), v in zip(self.buffer_updates, buf_vals):
            buf._data = v
        return fetches


class Executor:
    """reference: paddle.static.Executor (fluid/executor.py:1065)."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place
        self._cache: Dict[tuple, _CompiledProgram] = {}
        self.telemetry = tracing.StepTelemetry("static")

    def run(self, program=None, feed=None, fetch_list=None,
            feed_var_name="feed", fetch_var_name="fetch", scope=None,
            return_numpy=True, use_program_cache=True):
        program = program if program is not None else default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        if not isinstance(fetch_list, (list, tuple)):
            fetch_list = [fetch_list]
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list]

        if not program.ops:
            # startup program: parameters already initialized eagerly at
            # layer construction (see SURVEY §7 — one Tensor type); nothing
            # to do unless re-init thunks are recorded.
            return [] if fetch_names else None

        feed_names = sorted(feed)
        feed_arrays = []
        for n in feed_names:
            v = feed[n]
            arr = v._data if isinstance(v, Tensor) else np.asarray(v)
            feed_arrays.append(arr)
        train = program.optimize_directive is not None
        opt_id = id(program.optimize_directive[0]) if train else 0
        # ASP decoration is part of the compiled step (asp_idx baked in
        # _CompiledProgram.__init__): decorating AFTER a first run must
        # miss the cache, so the flag is in the key
        asp_on = train and bool(getattr(program.optimize_directive[0],
                                        "_asp_decorated", False))
        key = (id(program), program.version, tuple(feed_names),
               tuple(tuple(np.asarray(a).shape) + (str(np.asarray(a).dtype),)
                     for a in feed_arrays),
               tuple(fetch_names), train, opt_id, asp_on)
        # telemetry signature == the executable-cache key: a miss here is
        # exactly one program construction + first-call XLA compile
        with self.telemetry.step(key):
            cp = self._cache.get(key)
            if cp is None:
                cp = _CompiledProgram(program, feed_names, fetch_names,
                                      train)
                self._cache[key] = cp
            results = cp.run(feed_arrays)
        if return_numpy:
            return [np.asarray(r) for r in results]
        return [Tensor(r, _internal=True) for r in results]

    # -- dataset trainer loop (reference: fluid/executor.py
    # train_from_dataset:1769 / infer_from_dataset over TrainerDesc +
    # DeviceWorker RunFromDataset; here the "device worker" is the cached
    # compiled program and the loop feeds dataset batches) ------------------
    def _dataset_feed(self, dataset, batch):
        feed = {}
        for name, (offs, vals) in zip(dataset.slots(), batch):
            offs = np.asarray(offs)
            lens = np.diff(offs)
            if lens.size and (lens == lens[0]).all():
                k = int(lens[0])
                arr = np.asarray(vals).reshape(len(lens), k)
            else:
                raise NotImplementedError(
                    f"slot {name!r} is ragged across the batch; dense "
                    "slots only — express variable length via padding + "
                    "mask (SURVEY §7 LoD translation)")
            feed[name] = arr
        return feed

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """reference: executor.py:1769 — iterate the dataset, run the
        program's fused train step per batch."""
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        program = program if program is not None else default_main_program()
        if program.optimize_directive is None:
            raise ValueError(
                "train_from_dataset: program has no optimizer; call "
                "optimizer.minimize(loss) first")
        fetch_list = fetch_list or []
        names = fetch_info or [getattr(f, "name", str(f))
                               for f in fetch_list]
        for step, batch in enumerate(dataset):
            # fetch (device->host sync) only on print steps — the fused
            # train step otherwise runs without materializing values
            # (reference: trainer only prints fetches each print_period)
            want = (fetch_list if debug and fetch_list
                    and step % print_period == 0 else [])
            vals = self.run(program, feed=self._dataset_feed(dataset, batch),
                            fetch_list=want)
            if want:
                msg = ", ".join(f"{n}={np.asarray(v).ravel()[:4]}"
                                for n, v in zip(names, vals))
                print(f"[train_from_dataset] step {step}: {msg}")
        return None

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """reference: executor.py infer_from_dataset — same loop, no
        optimizer step (the program must not carry an optimize
        directive)."""
        if dataset is None:
            raise ValueError("infer_from_dataset needs a dataset")
        program = program if program is not None else default_main_program()
        if program.optimize_directive is not None:
            program = program.clone(for_test=True)
        outs = []
        for batch in dataset:
            outs.append(self.run(
                program, feed=self._dataset_feed(dataset, batch),
                fetch_list=fetch_list))
        return outs

    def close(self):
        self._cache.clear()
