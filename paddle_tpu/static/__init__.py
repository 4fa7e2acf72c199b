"""paddle.static parity: deferred program construction + Executor.

Reference capability: python/paddle/static/__init__.py + base/executor.py:1179
(Executor.run(feed, fetch_list)) + the program_guard/data builders. The
"programs as artifacts you build, inspect, and feed later" workflow:

    paddle.enable_static()
    main = static.Program()
    with static.program_guard(main):
        x = static.data('x', [None, 4], 'float32')
        y = my_layer(x)                 # ops record instead of executing
        loss = paddle.mean(y)
    exe = static.Executor()
    exe.run(static.default_startup_program())
    (out,) = exe.run(main, feed={'x': arr}, fetch_list=[loss])

TPU-native redesign (see ir.py): recorded ops are pure JAX fns; Executor
compiles the whole fetch closure with jax.jit (the PIR pass stack + CINN
collapse into XLA); parameters created by nn Layers during build stay
*eager* (initialized at creation — the startup program is a no-op run for
API parity) and are read live at each run, so optimizer updates between
runs behave like the reference's scope-backed weights.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtype import convert_dtype
from ..core.tensor import Parameter, Tensor
from ..jit.api import InputSpec  # noqa  (paddle.static.InputSpec)
from .ir import Operator, Program, Var, _ParamRef
from .passes import (PassManager, constant_folding,  # noqa
                     dead_code_elimination, prune_for_fetch)

__all__ = [
    "Program", "program_guard", "default_main_program",
    "default_startup_program", "data", "Executor", "append_backward",
    "save_inference_model", "load_inference_model", "InputSpec",
    "global_scope", "scope_guard", "name_scope", "cpu_places", "Variable",
    "PassManager", "constant_folding", "dead_code_elimination",
    "prune_for_fetch", "nn",
]

from .compat import *  # noqa: F401,F403,E402
from .compat import __all__ as _compat_all
from ..core import enforce as E

__all__ += list(_compat_all)

Variable = Var

_default_main = Program()
_default_startup = Program()
_prog_stack: List[Program] = []


def default_main_program() -> Program:
    return _prog_stack[-1] if _prog_stack else _default_main


def default_startup_program() -> Program:
    return _default_startup


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    """reference: static/__init__.py program_guard."""
    _prog_stack.append(main_program)
    try:
        yield
    finally:
        _prog_stack.pop()


def data(name: str, shape: Sequence[int], dtype="float32", lod_level=0):
    """reference: static/input.py data — a feed placeholder."""
    prog = default_main_program()
    return prog.add_feed(name, shape, convert_dtype(dtype))


# -- scope shims (parameters live eagerly; scope is an API-parity no-op) ----
class _Scope:
    def var(self, name):
        return None

    def find_var(self, name):
        return None


_global_scope = _Scope()


def global_scope():
    return _global_scope


@contextlib.contextmanager
def scope_guard(scope):
    yield


@contextlib.contextmanager
def name_scope(prefix):
    yield


def cpu_places(device_count=None):
    return ["cpu"]


class Executor:
    """reference: base/executor.py:1179. ``place`` is accepted for parity;
    placement is XLA's concern."""

    def __init__(self, place=None):
        self.place = place

    def run(self, program: Optional[Program] = None, feed=None,
            fetch_list=None, return_numpy=True):
        if program is None:
            program = default_main_program()
        if program is _default_startup or not program.ops():
            # startup program: parameters were initialized eagerly at
            # layer construction — nothing to run (documented delta)
            return []
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_vars = []
        for f in fetch_list:
            if isinstance(f, Tensor) and f._symbolic is not None:
                fetch_vars.append(f._symbolic)
            elif isinstance(f, Var):
                fetch_vars.append(f)
            else:
                raise TypeError(f"fetch_list entries must be program vars; "
                                f"got {type(f)}")
        outs = program.run(feed, fetch_vars)
        if return_numpy:
            return [np.asarray(o) for o in outs]
        return [Tensor(o) for o in outs]

    def close(self):
        pass


def append_backward(loss, parameter_list=None, no_grad_set=None):
    """reference: base/backward.py append_backward — appends one grad
    operator computing d(loss)/d(param) for every trainable parameter used
    by the forward program; returns [(param, grad_var)].

    The grad op's fn is jax.grad over a replay of the forward subgraph, so
    the compiled fetch of a grad var is the XLA backward program."""
    var = getattr(loss, "_symbolic", None)
    if var is None:
        raise E.InvalidArgumentError("append_backward needs a program (symbolic) loss")
    prog: Program = var.program
    fwd_ops = list(prog.global_block.ops)

    # ALL parameters the forward touches become grad-op inputs (frozen
    # ones included — they must be live jit inputs, not baked constants,
    # so later updates to them are seen by cached grad executables);
    # differentiation targets are the filtered subset.
    all_refs = prog.param_refs(fwd_ops)
    refs = list(all_refs)
    if parameter_list is not None:
        wanted = {id(p) for p in parameter_list}
        refs = [r for r in refs if id(r.param) in wanted]
    if no_grad_set:
        blocked = {id(p) for p in no_grad_set}
        refs = [r for r in refs if id(r.param) not in blocked]
    refs = [r for r in refs if not r.param.stop_gradient]
    if not refs:
        return []
    diff_pos = [i for i, r in enumerate(all_refs) if r in refs]

    feed_vars = [v for v in prog.feed_vars.values()]
    n_feed = len(feed_vars)
    fetch = [var]

    def grad_fn(*vals):
        feed_vals = vals[:n_feed]
        param_vals = list(vals[n_feed:])            # all_refs order

        def forward(diff_vals):
            override = {id(r.param): a
                        for r, a in zip(all_refs, param_vals)}
            for i, a in zip(diff_pos, diff_vals):
                override[id(all_refs[i].param)] = a
            env = {v.name: fv for v, fv in zip(feed_vars, feed_vals)}
            (lv,) = prog._replay_env(env, fetch, param_overrides=override,
                                     ops=fwd_ops)
            return jnp.sum(lv)

        grads = jax.grad(forward)([param_vals[i] for i in diff_pos])
        return tuple(grads)

    template: List[Any] = [None] * n_feed + list(all_refs)
    out_structs = [jax.ShapeDtypeStruct(tuple(r.param._data.shape),
                                        r.param._data.dtype) for r in refs]
    blk = prog.global_block
    outputs = []
    for r, ss in zip(refs, out_structs):
        gname = prog.new_var_name(f"{getattr(r.param, 'name', 'param')}@GRAD")
        gvar = Var(gname, ss.shape, ss.dtype, prog)
        blk.vars[gname] = gvar
        outputs.append(gvar)
    op = Operator("grad", grad_fn, template, list(range(n_feed)), {},
                  feed_vars, outputs)
    for i, v in enumerate(outputs):
        v.producer, v.slot = op, i
    blk.ops.append(op)
    return [(r.param, gv) for r, gv in zip(refs, outputs)]


# ---------------------------------------------------------------------------
# inference artifacts (reference: static/io.py save_inference_model)
# ---------------------------------------------------------------------------

def save_inference_model(path_prefix: str, feed_vars, fetch_vars, executor,
                         **kwargs):
    """Serialize the program slice feeding `fetch_vars` as a hermetic
    StableHLO artifact + weights (reference: static/io.py
    save_inference_model -> .pdmodel/.pdiparams)."""
    import pickle

    prog = None
    fvars = []
    for f in fetch_vars if isinstance(fetch_vars, (list, tuple)) else [fetch_vars]:
        v = f._symbolic if isinstance(f, Tensor) else f
        fvars.append(v)
        prog = v.program
    feeds = []
    for f in feed_vars if isinstance(feed_vars, (list, tuple)) else [feed_vars]:
        v = f._symbolic if isinstance(f, Tensor) else f
        feeds.append(v)

    def pure(*feed_arrays):
        env = {v.name: a for v, a in zip(feeds, feed_arrays)}
        return prog._replay_env(env, fvars)

    # None dims from static.data export as symbolic dims (shared per axis
    # position, as in jit.save) so the artifact stays batch-polymorphic
    scope = jax.export.SymbolicScope()
    syms = {}
    specs = []
    for v in feeds:
        dims = []
        for i, d in enumerate(v.shape):
            if i in v.none_axes:
                if i not in syms:
                    syms[i] = jax.export.symbolic_shape(
                        f"dyn_d{i}", scope=scope)[0]
                dims.append(syms[i])
            else:
                dims.append(int(d))
        specs.append(jax.ShapeDtypeStruct(tuple(dims), v.dtype))
    exported = jax.export.export(jax.jit(pure))(*specs)
    import os
    os.makedirs(os.path.dirname(os.path.abspath(path_prefix)) or ".",
                exist_ok=True)
    with open(path_prefix + ".pdmodel", "wb") as f:
        f.write(exported.serialize())
    with open(path_prefix + ".pdmeta", "wb") as f:
        pickle.dump({"feed_names": [v.name for v in feeds],
                     "fetch_names": [v.name for v in fvars]}, f)


class _LoadedProgram:
    """Deserialized inference program: run(feed, fetch) like an Executor
    target."""

    def __init__(self, exported, feed_names, fetch_names):
        self._exported = exported
        self.feed_names = feed_names
        self.fetch_names = fetch_names

    def run(self, feed: Dict[str, Any]):
        args = [jnp.asarray(np.asarray(feed[n])) for n in self.feed_names]
        return [np.asarray(o) for o in self._exported.call(*args)]


def load_inference_model(path_prefix: str, executor, **kwargs):
    """reference: static/io.py load_inference_model — returns
    [program, feed_target_names, fetch_targets]."""
    import pickle
    with open(path_prefix + ".pdmodel", "rb") as f:
        exported = jax.export.deserialize(f.read())
    with open(path_prefix + ".pdmeta", "rb") as f:
        meta = pickle.load(f)
    prog = _LoadedProgram(exported, meta["feed_names"], meta["fetch_names"])
    return [prog, meta["feed_names"], meta["fetch_names"]]


from . import nn  # noqa: E402  (static.nn layer builders)



class Scope:
    """Variable scope (reference: core Scope exposed as
    paddle.static.Scope): name -> host value. The executor's feed/fetch
    path owns real variable storage; Scope exists for tooling that
    expects to create/find named vars."""

    def __init__(self):
        self._vars: Dict[str, Any] = {}

    def var(self, name):
        self._vars.setdefault(name, None)
        return _ScopeVar(self, name)

    def find_var(self, name):
        return _ScopeVar(self, name) if name in self._vars else None

    def drop_kids(self):
        self._vars.clear()


class _ScopeVar:
    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return self._scope._vars.get(self._name)

    def set(self, value, place=None):
        self._scope._vars[self._name] = value


def global_scope():
    global _GLOBAL_SCOPE
    try:
        return _GLOBAL_SCOPE
    except NameError:
        _GLOBAL_SCOPE = Scope()
        return _GLOBAL_SCOPE


def scope_guard(scope):
    """Parity shim: context manager swapping the global scope."""
    import contextlib

    @contextlib.contextmanager
    def _guard():
        global _GLOBAL_SCOPE
        old = global_scope()
        _GLOBAL_SCOPE = scope
        try:
            yield
        finally:
            _GLOBAL_SCOPE = old
    return _guard()


def save_vars(executor, dirname, main_program=None, vars=None,
              predicate=None, filename=None):
    """Save program parameters to ``dirname`` (reference:
    static/io.py save_vars; single-file form with ``filename``)."""
    import os

    from ..framework.io import save
    prog = main_program or default_main_program()
    live = {getattr(r.param, "name", f"param_{i}"): r.param
            for i, r in enumerate(prog.param_refs())}
    if vars is not None:
        keep = {getattr(v, "name", v) for v in vars}
        live = {k: v for k, v in live.items() if k in keep}
    params = {k: np.asarray(v._data) for k, v in live.items()}
    os.makedirs(dirname, exist_ok=True)
    if filename:
        save(params, os.path.join(dirname, filename))
    else:
        for k, v in params.items():
            save({k: v}, os.path.join(dirname, k))


def load_vars(executor, dirname, main_program=None, vars=None,
              predicate=None, filename=None):
    """Inverse of save_vars (reference: static/io.py load_vars)."""
    import os

    from ..framework.io import load
    prog = main_program or default_main_program()
    live = {getattr(r.param, "name", f"param_{i}"): r.param
            for i, r in enumerate(prog.param_refs())}
    if filename:
        blobs = load(os.path.join(dirname, filename))
        if vars is not None:
            keep = {getattr(v, "name", v) for v in vars}
            blobs = {k: v for k, v in blobs.items() if k in keep}
    else:
        blobs = {}
        names = ([getattr(v, "name", v) for v in vars] if vars is not None
                 else list(live))
        for k in names:
            p = os.path.join(dirname, k)
            if os.path.exists(p):
                blobs.update(load(p))
    for name, param in live.items():
        if name in blobs:
            param.set_value(np.asarray(blobs[name]))


from .. import amp  # noqa: E402,F401  (paddle.static.amp parity alias)
__all__ += ["Scope", "global_scope", "scope_guard", "save_vars",
            "load_vars", "amp"]
