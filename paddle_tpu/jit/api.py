"""paddle.jit — the compiled path (to_static / save / load).

Reference: python/paddle/jit/api.py (to_static:136, save, load) and the
dy2st machinery (SURVEY.md §2.3). TPU-native redesign:

- **Capture** is trace-based: the eager Layer/function runs once under
  ``jax.jit`` tracing with parameter/buffer handles temporarily rebound to
  tracers (the Tensor facade is a pytree, so the SAME model code serves both
  modes — no AST transpile or bytecode hook needed; those exist in the
  reference because torch-style mutation can't trace, our ops are pure).
- **Program cache** keyed by input shapes/dtypes/training-flag mirrors the
  reference's _ExecutorCache (base/executor.py:857): new input signature →
  new traced program (the reference's dynamic-shape buckets).
- **Autograd**: a to_static call in training mode is ONE tape node whose
  backward is the compiled vjp of the whole program — the static-graph
  backward of the reference (append_backward) collapses into jax.vjp of the
  jitted function; XLA compiles both passes.
- **Buffers** (BN stats etc.) are threaded as extra outputs and written back
  after each call, keeping in-place semantics without mutation inside jit.
"""
from __future__ import annotations

import functools
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from ..core import enforce as E
from ..core import state
from ..core.dtype import convert_dtype
from ..core.tensor import Parameter, Tensor
from ..nn.layer.base import Layer

__all__ = ["to_static", "not_to_static", "InputSpec", "StaticFunction",
           "save", "load", "TranslatedLayer", "enable_to_static"]

_to_static_enabled = True


def enable_to_static(flag: bool):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


class InputSpec:
    """paddle.static.InputSpec parity (shape may contain None: resolved at
    first trace; each distinct concrete signature compiles once).

    DimExpr-lite (reference: paddle/pir/include/dialect/shape/): a dim
    may be a NAME string instead of None — the same name appearing on
    two axes (of one or several inputs) asserts they are equal at every
    call, and ``to_static(constraints=[...])`` can relate names
    arithmetically ("S % 8 == 0"). Named dims also export as SHARED
    symbolic dims in jit.save."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape)
        for d in self.shape:
            if not (d is None or isinstance(d, (int, str))):
                raise E.InvalidArgumentError(
                    f"InputSpec dim must be int, None, or a symbolic "
                    f"name string; got {d!r}")
        self.dtype = convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def _sig_of(x) -> tuple:
    if isinstance(x, Tensor):
        return ("T", tuple(x._data.shape), str(x._data.dtype),
                bool(x.stop_gradient))
    if isinstance(x, (jax.Array, np.ndarray)):
        return ("A", tuple(x.shape), str(x.dtype))
    if isinstance(x, (list, tuple)):
        return ("L", tuple(_sig_of(v) for v in x))
    if isinstance(x, dict):
        return ("D", tuple(sorted((k, _sig_of(v)) for k, v in x.items())))
    return ("P", repr(x))


class _Program:
    """One traced+compiled specialization (reference: a PIR Program +
    PirInterpreter instance in the _ExecutorCache)."""

    def __init__(self, jitted, out_tree_store):
        self.jitted = jitted
        self.out_tree_store = out_tree_store


class _GraphBreak(Exception):
    """Raised at trace time when the user function branches on a tensor
    value; full_graph=False converts it into an eager fallback (the
    reference's SOT graph-break semantics)."""


class StaticFunction:
    """Callable wrapper produced by ``to_static``
    (reference: dy2static/program_translator.py StaticFunction).

    ``bucket_batch=True`` enables batch-dim bucketing for INFERENCE
    paths: inputs whose leading dim varies are padded up to the next
    power-of-two bucket so XLA compiles one program per bucket instead
    of one per concrete batch — the TPU-native answer to the reference's
    symbolic-shape engine (static shapes, bounded recompiles). Outputs
    carrying the padded batch are sliced back.

    Contract: outputs must be row-wise in the batch — cross-batch
    reductions (batch-mean losses, BatchNorm training stats) would see
    the zero pad rows. When gradient recording is live the padding is
    skipped automatically (training uses exact shapes)."""

    def __init__(self, fn: Callable, layer: Optional[Layer] = None,
                 input_spec=None, build_strategy=None, full_graph=True,
                 bucket_batch=False, bucket_sizes=None,
                 bucket_seq=False, seq_axis=1, seq_bucket_sizes=None,
                 seq_pad_value=0, constraints=None):
        self._fn = fn
        self._layer = layer
        self._input_spec = input_spec
        # DimExpr-lite: named dims in input_spec + relational constraints
        from .constraints import DimConstraints
        self._constraints = DimConstraints(constraints) \
            if (constraints or self._spec_dim_names(input_spec)) else None
        if constraints and not self._spec_dim_names(input_spec):
            missing = self._constraints.names
            if missing:
                # constraints can only bind through named spec dims
                raise E.InvalidArgumentError(
                    f"to_static(constraints=...) names dims {sorted(missing)} "
                    "but input_spec declares no named dims",
                    hint="use InputSpec([None, 'S'], ...) style names")
        self._programs: Dict[tuple, _Program] = {}
        self._bucket_batch = bool(bucket_batch)
        self._bucket_sizes = sorted(bucket_sizes) if bucket_sizes else None
        self._bucket_seq = bool(bucket_seq)
        self._seq_axis = int(seq_axis)
        self._seq_bucket_sizes = sorted(seq_bucket_sizes)             if seq_bucket_sizes else None
        self._seq_pad_value = seq_pad_value
        # full_graph=False: a capture failure (data-dependent Python
        # branch) becomes a graph break — that signature runs eagerly
        # with a one-time warning, like the reference's SOT fallback.
        self._full_graph = bool(full_graph)
        self._eager_keys: set = set()
        self._segmented_keys: set = set()
        self._segmented = None
        # introspection-registry identity, assigned on first use: the
        # registry's records outlive this object, so they are keyed by
        # a process-unique uid, never id(self) (address reuse would
        # alias a successor function onto stale records)
        self._registry_uid = None
        functools.update_wrapper(self, fn)

    @staticmethod
    def _spec_dim_names(input_spec):
        """All symbolic dim names declared across the input specs."""
        names = set()
        for s in (input_spec or []):
            if isinstance(s, InputSpec):
                names.update(d for d in s.shape if isinstance(d, str))
        return names

    def _axis_name(self, axis: int):
        """The symbolic name bound to ``axis`` (first spec declaring
        one), or None — used to aim constraint pruning at the bucketed
        axis."""
        for s in (self._input_spec or []):
            if isinstance(s, InputSpec) and len(s.shape) > axis \
                    and isinstance(s.shape[axis], str):
                return s.shape[axis]
        return None

    def _check_dims(self, args):
        """Bind named spec dims against the call's concrete shapes;
        raise typed errors on name conflicts (the dim_a == dim_b
        relation) and on violated constraints."""
        if self._constraints is None:
            return
        bindings: dict = {}
        for spec, a in zip(self._input_spec or [], args):
            if not (isinstance(spec, InputSpec) and isinstance(a, Tensor)):
                continue
            shape = a._data.shape
            if len(spec.shape) != len(shape):
                raise E.InvalidArgumentError(
                    f"input rank {len(shape)} does not match "
                    f"InputSpec {spec.shape}")
            for axis, d in enumerate(spec.shape):
                if isinstance(d, int) and d >= 0 and d != shape[axis]:
                    raise E.InvalidArgumentError(
                        f"input dim {axis} is {shape[axis]}, InputSpec "
                        f"fixes it to {d}")
                if isinstance(d, str):
                    seen = bindings.setdefault(d, int(shape[axis]))
                    if seen != int(shape[axis]):
                        raise E.InvalidArgumentError(
                            f"symbolic dim {d!r} bound to both {seen} "
                            f"and {shape[axis]} in one call",
                            hint="the same name on two axes asserts "
                                 "they are equal (DimExpr relation)")
        self._constraints.check(bindings)

    def _admit_fn(self, axis: int):
        """Bucket-size predicate from the unary constraints on the
        name bound to ``axis``, or None when unconstrained."""
        if self._constraints is None:
            return None
        name = self._axis_name(axis)
        if name is None or name not in self._constraints.names:
            return None
        return lambda b: self._constraints.admits(name, b)

    @staticmethod
    def _pick_bucket(n: int, sizes, admit=None) -> int:
        if sizes:
            for b in sizes:
                if n <= b and (admit is None or admit(b)):
                    return b
            return n          # beyond the largest bucket: run unbucketed
        b = 1
        while b < n:
            b <<= 1
        if admit is not None and not admit(b):
            # the power-of-two ladder violates a unary constraint on
            # this dim (e.g. "S % 96 == 0"): take the smallest admitted
            # size >= n within a bounded scan, else run unbucketed (the
            # real size already passed _check_dims)
            for c in range(n, 4 * b + 1):
                if admit(c):
                    return c
            return n
        return b

    def _bucket_of(self, n: int) -> int:
        return self._pick_bucket(n, self._bucket_sizes,
                                 admit=self._admit_fn(0))

    def _apply_bucketing(self, args):
        """Pad every Tensor arg's leading dim from the common batch size
        to its bucket; returns (padded_args, real_batch or None,
        padded_batch).

        Bucketing is an INFERENCE-path feature (serving variable batch):
        the padded rows flow through the function, so outputs must be
        row-wise in the batch; and because padding rebuilds inputs, it
        only engages while grad recording is off (paddle.no_grad() /
        eval serving) — training always uses exact shapes (correct beats
        fewer compiles). Closure-captured parameters are invisible here,
        so grad state is the only safe gate."""
        if state.grad_enabled():
            return args, None, None
        batches = {a._data.shape[0] for a in args
                   if isinstance(a, Tensor) and a._data.ndim > 0}
        if len(batches) != 1:
            return args, None, None
        (n,) = batches
        b = self._bucket_of(int(n))
        if b == n:
            return args, None, None
        import jax.numpy as _jnp

        def pad(a):
            if isinstance(a, Tensor) and a._data.ndim > 0 \
                    and a._data.shape[0] == n:
                widths = [(0, b - n)] + [(0, 0)] * (a._data.ndim - 1)
                return Tensor(_jnp.pad(a._data, widths))
            return a
        return tuple(pad(a) for a in args), int(n), int(b)

    def _seq_bucket_of(self, n: int) -> int:
        return self._pick_bucket(n, self._seq_bucket_sizes,
                                 admit=self._admit_fn(self._seq_axis))

    def _apply_seq_bucketing(self, args):
        """Pad the sequence axis to its bucket (the reference's dynamic
        seq-len bucketing policy for serving). SOUND for causal /
        right-context-free computations only: right-padding cannot
        change the outputs at real positions of a causal model (position
        i attends to <= i), so slicing the pad tail back off is EXACT —
        no mask plumbing needed. Non-causal models must consume an
        explicit mask themselves or keep bucket_seq off. Inference-only
        like batch bucketing (skipped while grads record).

        Coincidence hazard (like batch bucketing's): any output whose
        ``seq_axis`` dim equals the padded bucket is sliced — a feature
        dim that lands exactly on a bucket (both are often powers of
        two) would be truncated. Choose ``seq_bucket_sizes`` that avoid
        the model's feature dims when outputs mix axes."""
        if state.grad_enabled():
            return args, None, None
        axis = self._seq_axis
        lens = {a._data.shape[axis] for a in args
                if isinstance(a, Tensor) and a._data.ndim > axis}
        if len(lens) != 1:
            return args, None, None
        (n,) = lens
        b = self._seq_bucket_of(int(n))
        if b == n:
            return args, None, None
        import jax.numpy as _jnp

        def pad(a):
            if isinstance(a, Tensor) and a._data.ndim > axis                     and a._data.shape[axis] == n:
                widths = [(0, 0)] * a._data.ndim
                widths[axis] = (0, b - n)
                return Tensor(_jnp.pad(a._data, widths,
                                       constant_values=self._seq_pad_value))
            return a
        return tuple(pad(a) for a in args), int(n), int(b)

    # -- helpers -------------------------------------------------------------
    def _named_params(self):
        if self._layer is None:
            return []
        return [(n, p) for n, p in self._layer.named_parameters()
                if p is not None]

    def _named_buffers(self):
        if self._layer is None:
            return []
        return [(n, b) for n, b in self._layer.named_buffers()
                if b is not None]

    def _cache_key(self, args, kwargs):
        training = self._layer.training if self._layer is not None else False
        return (_sig_of(args), _sig_of(kwargs), training,
                tuple(str(p._data.dtype) for _, p in self._named_params()))

    def _build_program(self, args, kwargs) -> _Program:
        named_params = self._named_params()
        named_buffers = self._named_buffers()
        fn = self._fn
        out_store: dict = {}

        def pure(param_arrays, buffer_arrays, arg_arrays, kwarg_arrays):
            # Rebind handles to tracers for the duration of the trace,
            # restore after (the handles belong to live eager objects).
            saved_p = [(p, p._data) for _, p in named_params]
            saved_b = [(b, b._data) for _, b in named_buffers]
            try:
                for (n, p) in named_params:
                    p._data = param_arrays[n]
                for (n, b) in named_buffers:
                    b._data = buffer_arrays[n]
                with state.functional_mode():
                    try:
                        out = fn(*arg_arrays, **kwarg_arrays)
                    except (jax.errors.TracerBoolConversionError,
                            jax.errors.ConcretizationTypeError) as e:
                        raise _GraphBreak(
                            "to_static: the function branches on a tensor "
                            "VALUE, which trace-based capture cannot "
                            "record (the reference's SOT guards exist for "
                            "this — jit/sot/translate.py). Rewrite the "
                            "branch with paddle_tpu.where / lax.cond, or "
                            "keep it out of the to_static region. Python "
                            "branches on non-tensor values are baked at "
                            "trace time per input signature. "
                            "(full_graph=False falls back to eager "
                            "execution instead of raising — the "
                            "reference's SOT graph-break behavior.)") from e
                new_buffers = {n: b._data for n, b in named_buffers}
                flat, tree = jax.tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                flat = [o._data if isinstance(o, Tensor) else o for o in flat]
                out_store["tree"] = tree
                out_store["n_out"] = len(flat)
                return tuple(flat), new_buffers
            finally:
                for p, d in saved_p:
                    p._data = d
                for b, d in saved_b:
                    b._data = d

        return _Program(jax.jit(pure), out_store)

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            return self._fn(*args, **kwargs)
        self._check_dims(args)
        real_batch = None
        seq_pad = None
        if self._bucket_batch and not kwargs:
            args, real_batch, padded_batch = self._apply_bucketing(args)
        if self._bucket_seq and not kwargs:
            args, real_seq, padded_seq = self._apply_seq_bucketing(args)
            if real_seq is not None:
                seq_pad = (self._seq_axis, real_seq, padded_seq)
        if seq_pad is not None and real_batch is None:
            out = self.__wrapped_call(args, kwargs)
            return self._unpad_seq(out, *seq_pad)
        if real_batch is not None:
            out = self.__wrapped_call(args, kwargs)
            # Ranks of the padded inputs: an output that is batch-major
            # normally keeps one of these ranks. Slicing an output whose
            # leading dim merely COINCIDES with the bucket size (e.g. a
            # [num_classes, ...] table where num_classes == bucket) would
            # silently truncate it — warn when the rank heuristic says the
            # sliced output doesn't look like any padded input.
            in_ranks = {a._data.ndim for a in args
                        if isinstance(a, Tensor) and a._data.ndim > 0}
            odd_ranks = []

            def unpad(o):
                if isinstance(o, Tensor) and o._data.ndim > 0 \
                        and o._data.shape[0] == padded_batch:
                    # Reduced-rank outputs ([B] predictions from [B, F]
                    # inputs) are normal batch-major shapes; only an
                    # output of HIGHER rank than every padded input looks
                    # like a non-batch table caught by coincidence.
                    if o._data.ndim > max(in_ranks):
                        odd_ranks.append(o._data.ndim)
                    return Tensor(o._data[:real_batch])
                return o
            out = jax.tree_util.tree_map(
                unpad, out, is_leaf=lambda x: isinstance(x, Tensor))
            if odd_ranks:   # warn AFTER tree_map so file:line is the caller
                import warnings

                warnings.warn(
                    "to_static bucketing: sliced output(s) of rank(s) "
                    f"{sorted(set(odd_ranks))} whose leading dim == bucket "
                    f"size {padded_batch} but whose rank matches no padded "
                    "input — if such an output is not batch-major, disable "
                    "bucket_batch for this function", stacklevel=2)
            if seq_pad is not None:
                out = self._unpad_seq(out, *seq_pad)
            return out
        return self.__wrapped_call(args, kwargs)

    def _unpad_seq(self, out, axis, real, padded):
        def unpad(o):
            if isinstance(o, Tensor) and o._data.ndim > axis                     and o._data.shape[axis] == padded:
                idx = [slice(None)] * o._data.ndim
                idx[axis] = slice(0, real)
                return Tensor(o._data[tuple(idx)])
            return o
        return jax.tree_util.tree_map(
            unpad, out, is_leaf=lambda x: isinstance(x, Tensor))

    def __wrapped_call(self, args, kwargs):
        key = self._cache_key(args, kwargs)
        if key in self._eager_keys:
            return self._fn(*args, **kwargs)
        if key in self._segmented_keys:
            return self.__segmented_call(key, args, kwargs)
        try:
            return self.__compiled_call(key, args, kwargs)
        except _GraphBreak as e:
            if self._full_graph:
                raise E.PreconditionNotMetError(str(e)) from e
            import warnings

            # mixed capture (reference SOT, jit/sot/translate.py:30):
            # this signature now runs as compiled segments around the
            # eager island — in BOTH eval and training mode (taped
            # slices carry cached vjps, segment.py call_taped).
            self._segmented_keys.add(key)
            self._programs.pop(key, None)
            warnings.warn(
                "to_static: graph break in "
                f"{getattr(self._fn, '__name__', self._fn)} "
                "(data-dependent Python branch); this input "
                "signature runs as compiled segments around the "
                "branch (full_graph=False)", stacklevel=3)
            return self.__segmented_call(key, args, kwargs)

    def __segmented_call(self, key, args, kwargs):
        if self._segmented is None:
            from .segment import SegmentedFunction
            self._segmented = SegmentedFunction(self._fn, self._cache_key)
        from .segment import SegmentCaptureError
        try:
            return self._segmented(args, kwargs)
        except SegmentCaptureError as e:
            # recorder/replay-internal failure degrades to eager; the
            # user's own exceptions propagate (re-running fn here would
            # double-execute its side effects)
            import warnings

            warnings.warn(
                "to_static: segmented capture failed for "
                f"{getattr(self._fn, '__name__', self._fn)} ({e}); this "
                "input signature now runs eagerly", stacklevel=2)
            self._segmented_keys.discard(key)
            self._eager_keys.add(key)
            return self._fn(*args, **kwargs)

    def __compiled_call(self, key, args, kwargs):
        prog = self._programs.get(key)
        t_compile = None
        exec_rec = None
        if prog is None:
            if _monitor.enabled():
                # program-cache miss == a fresh trace+compile; a miss on
                # a StaticFunction that ALREADY holds programs is a
                # recompile (new input signature / training flip) — the
                # reference's _ExecutorCache growth events.
                _monitor.inc("jit.cache.miss",
                             doc="to_static program-cache misses")
                if self._programs:
                    _monitor.inc("jit.recompile",
                                 doc="cache misses after the first "
                                     "program (signature churn)")
                t_compile = time.perf_counter()
            prog = self._build_program(args, kwargs)
            self._programs[key] = prog
        elif _monitor.enabled():
            _monitor.inc("jit.cache.hit",
                         doc="to_static program-cache hits")
            from ..monitor import exectime as _exectime
            from ..monitor import programs as _programs
            _programs.note_hit(self._registry_key(key))
            # measured execution plane: 1-in-N sampled wall time of
            # HIT dispatches only (a miss's wall time is compile —
            # jit.compile_ms already owns it). The recorder blocks on
            # the sampled call's outputs below; unsampled calls and
            # the off path add zero synchronizations.
            exec_rec = _exectime.maybe_sample(self._registry_key(key))

        named_params = self._named_params()
        named_buffers = self._named_buffers()
        param_arrays = {n: p._data for n, p in named_params}
        buffer_arrays = {n: b._data for n, b in named_buffers}
        arg_arrays = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, args,
            is_leaf=lambda x: isinstance(x, Tensor))
        kwarg_arrays = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, kwargs,
            is_leaf=lambda x: isinstance(x, Tensor))

        trainable = [(n, p) for n, p in named_params if not p.stop_gradient]
        diff_args: List[Tuple[int, Tensor]] = [
            (i, a) for i, a in enumerate(args)
            if isinstance(a, Tensor) and not a.stop_gradient
            and jnp.issubdtype(a._data.dtype, jnp.inexact)]
        need_grad = state.grad_enabled() and (trainable or diff_args)

        if not need_grad:
            flat_out, new_buffers = prog.jitted(
                param_arrays, buffer_arrays, arg_arrays, kwarg_arrays)
            if exec_rec is not None:
                exec_rec((flat_out, new_buffers))
            compile_ms = self._note_compile(t_compile)
            if t_compile is not None:
                from ..monitor import mfu as _mfu
                cost = _mfu.lowered_cost(
                    prog.jitted, param_arrays, buffer_arrays,
                    arg_arrays, kwarg_arrays)
                _mfu.record_program_flops(cost["flops"],
                                          source="to_static")
                self._register_program(
                    key, prog, compile_ms, cost, param_arrays,
                    buffer_arrays, arg_arrays, kwarg_arrays)
        else:
            train_names = [n for n, _ in trainable]
            diff_idx = [i for i, _ in diff_args]

            def closed(train_arrays, diff_arg_arrays):
                pa = dict(param_arrays)
                pa.update(train_arrays)
                aa = list(arg_arrays)
                for i, arr in zip(diff_idx, diff_arg_arrays):
                    aa[i] = arr
                return prog.jitted(pa, buffer_arrays, tuple(aa),
                                   kwarg_arrays)

            train_arrays = {n: p._data for n, p in trainable}
            diff_arg_arrays = tuple(a._data for _, a in diff_args)
            (flat_out, new_buffers), vjp_fn = jax.vjp(
                closed, train_arrays, diff_arg_arrays)
            if exec_rec is not None:
                # the grad path re-traces the vjp composition per call,
                # so a sample here measures the TRAINING dispatch's
                # wall time (trace + forward execution) — the number a
                # drift detector actually wants for this seam
                exec_rec((flat_out, new_buffers))
            compile_ms = self._note_compile(t_compile)
            if t_compile is not None:
                # MFU accounting must count what a TRAINING call
                # executes — forward AND backward — so lower the same
                # vjp composition run above, not just prog.jitted
                # (forward alone under-counts ~3x). Falls back to the
                # forward program if the composed lowering can't be
                # analyzed.
                from ..monitor import mfu as _mfu

                def _full_step(ta, da):
                    out, inner_vjp = jax.vjp(closed, ta, da)
                    cts = jax.tree_util.tree_map(
                        _mfu.ones_cotangent, out)
                    # return out too: the real call materializes the
                    # forward results, so the analyzed program must
                    # keep them live (grads alone let XLA DCE any
                    # forward op the backward doesn't reuse)
                    return out, inner_vjp(cts)

                cost = _mfu.lowered_cost(
                    jax.jit(_full_step), train_arrays, diff_arg_arrays)
                if not cost["flops"]:
                    cost = _mfu.lowered_cost(
                        prog.jitted, param_arrays, buffer_arrays,
                        arg_arrays, kwarg_arrays)
                _mfu.record_program_flops(cost["flops"],
                                          source="to_static")
                self._register_program(
                    key, prog, compile_ms, cost, param_arrays,
                    buffer_arrays, arg_arrays, kwarg_arrays)

            input_tensors = [p for _, p in trainable] + \
                [a for _, a in diff_args]
            zero_bufs = {n: jnp.zeros_like(v)
                         for n, v in new_buffers.items()}

            def tape_vjp(cotangents):
                cts = cotangents if isinstance(cotangents, tuple) else \
                    (cotangents,)
                g_train, g_args = vjp_fn((tuple(cts), zero_bufs))
                return [g_train[n] for n in train_names] + list(g_args)

            from ..autograd import tape
            out_tensors = [Tensor(o) for o in flat_out]
            tape.record_node(f"to_static[{self._fn.__name__}]", tape_vjp,
                             input_tensors, out_tensors)
            for n, b in named_buffers:
                b._data = new_buffers[n]
            tree = prog.out_tree_store["tree"]
            wrapped = jax.tree_util.tree_unflatten(tree, out_tensors)
            return wrapped

        for n, b in named_buffers:
            b._data = new_buffers[n]
        tree = prog.out_tree_store["tree"]
        return jax.tree_util.tree_unflatten(
            tree, [Tensor(o) for o in flat_out])

    @staticmethod
    def _note_compile(t_compile):
        """Observe trace+compile latency for a cache-miss call (timed
        through the first execution, where jax.jit actually compiles);
        returns the ms (None on cache hits). The caller follows up with
        the MFU capture — the new program's XLA-cost-analysis FLOPs
        into ``jit.program.flops`` (one extra re-trace + HLO lowering
        per compile; no second XLA compile — see monitor/mfu.py) —
        lowering the grad-path vjp composition where one exists so
        training programs count fwd+bwd FLOPs — and the introspection-
        registry record (``_register_program``)."""
        if t_compile is None:
            return None
        ms = (time.perf_counter() - t_compile) * 1e3
        _monitor.observe(
            "jit.compile_ms", ms,
            doc="to_static trace+compile wall time per cache miss",
            buckets=tuple(float(10 ** i) / 10 for i in range(9)))
        return ms

    def _registry_key(self, key):
        if self._registry_uid is None:
            from ..monitor import programs as _programs
            self._registry_uid = _programs.next_uid()
        return ("to_static", self._registry_uid, key)

    def _register_program(self, key, prog, compile_ms, cost,
                          param_arrays, buffer_arrays, arg_arrays,
                          kwarg_arrays):
        """Feed the compiled-program introspection registry
        (monitor/programs.py) at the cache-miss seam: name, input
        signature, compile wall-ms, analyzed FLOPs + bytes-accessed
        (``cost`` = monitor.mfu.lowered_cost result), the per-leaf
        sharding summary of the concrete params/args (the ``/sharding``
        endpoint's per-program feed), and a LAZY memory+collective
        analyzer over the forward program's avals (the ``/programs`` /
        ``/roofline`` endpoints pay the one AOT compile, not this
        call). Grad-path programs record the forward program's memory
        breakdown — the executable this cache actually holds."""
        from ..monitor import programs as _programs
        args = (param_arrays, buffer_arrays, arg_arrays, kwarg_arrays)
        try:
            from ..distributed import introspect as _introspect
            sharding = _introspect.describe_tree(
                {"params": param_arrays, "args": arg_arrays,
                 "kwargs": kwarg_arrays})
        except Exception:
            sharding = None
        _programs.record_program(
            self._registry_key(key),
            getattr(self._fn, "__name__", "to_static"),
            source="to_static",
            signature=_programs.signature_of((arg_arrays, kwarg_arrays)),
            donated=(),
            compile_ms=round(compile_ms, 3)
            if compile_ms is not None else None,
            flops=cost["flops"],
            bytes_accessed=cost["bytes_accessed"],
            sharding=sharding,
            analyzer=_programs.analyzer_for(prog.jitted, args))

    @property
    def concrete_programs(self):
        return self._programs

    def rollback(self):
        return self._fn


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True, bucket_batch=False,
              bucket_sizes=None, bucket_seq=False, seq_axis=1,
              seq_bucket_sizes=None, seq_pad_value=0, constraints=None,
              **kwargs):
    """paddle.jit.to_static parity (reference: jit/api.py:136).
    ``bucket_batch``/``bucket_sizes``: see StaticFunction — pad variable
    leading dims to buckets so XLA recompiles O(log max_batch) times.
    ``bucket_seq``/``seq_axis``/``seq_bucket_sizes``/``seq_pad_value``:
    the same policy for the SEQUENCE axis (serving variable-length
    prompts with O(log max_len) compiles). Exact for causal models
    (right-padding cannot influence real positions); non-causal
    functions must consume a mask themselves. ``full_graph=False``:
    data-dependent Python branches run as compiled segments around the
    break (jit/segment.py) instead of erroring."""
    extra = dict(bucket_batch=bucket_batch, bucket_sizes=bucket_sizes,
                 bucket_seq=bucket_seq, seq_axis=seq_axis,
                 seq_bucket_sizes=seq_bucket_sizes,
                 seq_pad_value=seq_pad_value,
                 full_graph=full_graph, constraints=constraints)

    def decorate(obj):
        if isinstance(obj, Layer):
            sf = StaticFunction(obj.forward, layer=obj,
                                input_spec=input_spec, **extra)
            obj.forward = sf
            return obj
        layer = getattr(obj, "__self__", None)
        if isinstance(layer, Layer):
            return StaticFunction(obj, layer=layer, input_spec=input_spec,
                                  **extra)
        return StaticFunction(obj, layer=None, input_spec=input_spec,
                              **extra)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn.__not_to_static__ = True
    return fn


# ---------------------------------------------------------------------------
# save / load: StableHLO export (reference: jit.save -> .pdmodel/.pdiparams)
# ---------------------------------------------------------------------------

def _resolve_specs(layer, input_spec):
    """InputSpec dims of None export as *symbolic* dims (jax.export shape
    polymorphism) so the artifact serves any size on those axes — the
    dynamic-dim behavior of the reference's exported programs."""
    specs = []
    scope = jax.export.SymbolicScope()
    syms = {}

    def _dim(d, axis):
        if isinstance(d, str):
            # named symbolic dim (DimExpr-lite): shared across inputs
            # by NAME, so ids/mask pairs declared with the same name
            # export as one program-level symbol
            if d not in syms:
                syms[d] = jax.export.symbolic_shape(d, scope=scope)[0]
            return syms[d]
        if d is None or (isinstance(d, int) and d < 0):
            # One shared symbol per axis position: None batch dims of
            # different inputs must unify (ids/mask pairs broadcast
            # together), matching the reference where a dynamic dim is a
            # program-level symbol, not per-input.
            if axis not in syms:
                syms[axis] = jax.export.symbolic_shape(
                    f"dyn_d{axis}", scope=scope)[0]
            return syms[axis]
        return int(d)

    for s in input_spec:
        if isinstance(s, InputSpec):
            shape = tuple(_dim(d, i) for i, d in enumerate(s.shape))
            specs.append(jax.ShapeDtypeStruct(shape, s.dtype))
        elif isinstance(s, Tensor):
            specs.append(jax.ShapeDtypeStruct(tuple(s._data.shape),
                                              s._data.dtype))
        else:
            arr = jnp.asarray(s)
            specs.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
    return specs


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save parity: writes ``path.pdmodel`` (serialized StableHLO
    program via jax.export), ``path.pdiparams`` (weights), ``path.pdmeta``
    (treedefs). The artifact is hermetic: load() does not need the model
    class."""
    if isinstance(layer, StaticFunction):
        fn, owner = layer._fn, layer._layer
        input_spec = input_spec or layer._input_spec
    elif isinstance(layer, Layer):
        fwd = layer.forward
        if isinstance(fwd, StaticFunction):
            fn, owner = fwd._fn, layer
            input_spec = input_spec or fwd._input_spec
        else:
            fn, owner = fwd, layer
    else:
        fn, owner = layer, None

    if input_spec is None:
        raise E.InvalidArgumentError(
            "jit.save requires input_spec (pass it here or to to_static)")
    specs = _resolve_specs(owner, input_spec)

    named_params = [] if owner is None else \
        [(n, p) for n, p in owner.named_parameters()]
    named_buffers = [] if owner is None else \
        [(n, b) for n, b in owner.named_buffers()]
    if owner is not None:
        was_training = owner.training
        owner.eval()

    out_store = {}

    def pure(param_arrays, buffer_arrays, *arg_arrays):
        saved_p = [(p, p._data) for _, p in named_params]
        saved_b = [(b, b._data) for _, b in named_buffers]
        try:
            for (n, p) in named_params:
                p._data = param_arrays[n]
            for (n, b) in named_buffers:
                b._data = buffer_arrays[n]
            with state.functional_mode():
                out = fn(*arg_arrays)
            flat, tree = jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            out_store["tree_pickle"] = pickle.dumps(tree)
            return tuple(o._data if isinstance(o, Tensor) else o
                         for o in flat)
        finally:
            for p, d in saved_p:
                p._data = d
            for b, d in saved_b:
                b._data = d

    param_specs = {n: jax.ShapeDtypeStruct(tuple(p._data.shape),
                                           p._data.dtype)
                   for n, p in named_params}
    buffer_specs = {n: jax.ShapeDtypeStruct(tuple(b._data.shape),
                                            b._data.dtype)
                    for n, b in named_buffers}
    exported = jax.export.export(jax.jit(pure))(
        param_specs, buffer_specs, *specs)

    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".",
                exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        f.write(exported.serialize())
    from ..framework.io import save as fsave
    fsave({"params": {n: p for n, p in named_params},
           "buffers": {n: b for n, b in named_buffers}},
          path + ".pdiparams")
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump({"out_tree": out_store["tree_pickle"],
                     "n_inputs": len(specs)}, f)
    if owner is not None and was_training:
        owner.train()


class TranslatedLayer(Layer):
    """Deserialized inference program (reference:
    jit/translated_layer.py TranslatedLayer)."""

    def __init__(self, exported, params, buffers, out_tree):
        super().__init__()
        self._exported = exported
        self._param_arrays = {n: (p._data if isinstance(p, Tensor)
                                  else jnp.asarray(np.asarray(p)))
                              for n, p in params.items()}
        self._buffer_arrays = {n: (b._data if isinstance(b, Tensor)
                                   else jnp.asarray(np.asarray(b)))
                               for n, b in buffers.items()}
        for n, arr in self._param_arrays.items():
            self.add_parameter(n.replace(".", "__"), Parameter(arr))
        self._out_tree = out_tree

    def forward(self, *args):
        arg_arrays = [a._data if isinstance(a, Tensor) else jnp.asarray(a)
                      for a in args]
        flat = self._exported.call(self._param_arrays, self._buffer_arrays,
                                   *arg_arrays)
        return jax.tree_util.tree_unflatten(
            self._out_tree, [Tensor(o) for o in flat])


def load(path, **configs) -> TranslatedLayer:
    """paddle.jit.load parity."""
    with open(path + ".pdmodel", "rb") as f:
        exported = jax.export.deserialize(f.read())
    from ..framework.io import load as fload
    blob = fload(path + ".pdiparams")
    with open(path + ".pdmeta", "rb") as f:
        meta = pickle.load(f)
    out_tree = pickle.loads(meta["out_tree"])
    return TranslatedLayer(exported, blob["params"], blob["buffers"],
                           out_tree)
