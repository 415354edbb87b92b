"""Device time of the model's own layers: the scopes inside the LM tick.

A ``jax.named_scope`` entered in code that ``jax.grad`` differentiates
reaches an instruction's ``op_name`` wrapped in its transforms:
``.../jvp(lm.head)/...`` in the forward, ``.../transpose(jvp(lm.head))/...``
in the backward. Inside a ``jax.checkpoint`` body it stays plain
(``.../checkpoint/ssm.in_proj/...``, ``.../rematted_computation/ssm.in_proj/...``).
``bench.trace.scope_s`` keeps an op only if a path component equals the scope,
so it misses the wrapped forms; :func:`layer_s` peels the wrappers first.

:data:`LAYERS` splits the tick's model and optimizer work into five layers,
each a per-layer metric's name with the scopes it reads and what it is per.
No ``BENCHMARK.json`` entry reports them yet; a reader for one reads
``per, scopes = LAYERS[name]`` and returns ``layer_ms(ctx, per, *scopes)``.
"""
from __future__ import annotations

import re

from bench import trace as tr

_WRAP = re.compile(r"^[A-Za-z_][\w.]*\((.*)\)$")
# an op_name may hold more than one path, joined by ";"
_SEP = re.compile(r"[/;]")

# metric -> (per, scopes); "ticks" counts eval and retrain together, the
# forward, the remat forward and the backward
LAYERS = {
    "proj_ms.lm": ("ticks", ("ssm.in_proj", "ssm.out_proj")),
    "ssd_ms.lm": ("ticks", ("ssm.ssd",)),
    "pointwise_ms.lm": ("ticks", ("lm.norm", "ssm.conv", "ssm.gate_norm")),
    "head_ms.lm": ("ticks", ("lm.head", "lm.loss")),
    "adamw_ms.lm": ("retrains", ("train.adamw",)),
}


def path_names(path: str) -> set[str]:
    """The components of an op_name path, each with its transform
    wrappers (``jvp(x)``, ``transpose(jvp(x))``, any ``f(x)``) peeled off."""
    out = set()
    for part in _SEP.split(path):
        while m := _WRAP.match(part):
            part = m.group(1)
        out.add(part)
    return out


def layer_s(ctx, *names: str) -> float | None:
    """Device seconds per chip of the union of the ops whose op_name path
    has a component equal to one of ``names`` (:func:`path_names`); the
    union, so a ``while`` and its body count once. None when no instruction
    of the window's programs carries any of them: a program without the
    scopes."""
    want = set(names)
    keep = {module: {i for i, path in instrs.items() if want & path_names(path)}
            for module, instrs in ctx.scopes.items()}
    if not any(keep.values()):
        return None
    s = tr._union_s(ctx.trace, lambda o: o.name in keep.get(o.module, ()),
                    ctx.w0, ctx.w1)
    return s / max(ctx.trace.devices, 1)


def layer_ms(ctx, per: str, *names: str) -> float | None:
    """:func:`layer_s` in milliseconds per ``ctx.counts[per]`` (``"ticks"``
    or ``"retrains"``); None where :func:`layer_s` is, or where the window
    has none of ``per``."""
    if not ctx.counts[per]:
        return None
    s = layer_s(ctx, *names)
    return None if s is None else 1e3 * s / ctx.counts[per]
