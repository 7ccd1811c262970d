"""Test helper: what the executor would publish for the trace a test just
made of a Program (``observability/lowerings.py``), read back by label."""
from paddle_tpu.observability import lowerings
from paddle_tpu.observability.metrics import MetricsRegistry


def publish(program, label="p", registry=None):
    """Publish (and so empty) ``program``'s lowering reports under the
    program label ``label``, into ``registry`` or a fresh one; returns it."""
    registry = registry or MetricsRegistry()
    lowerings.publish(program._lowering_notes, label, registry)
    return registry


def read(registry, family, *names):
    """{the values of the labels ``names`` (one value, not a tuple, for one
    name): the metric's value}, over ``family``'s children in ``registry``;
    empty where nothing of the family was published."""
    metric = registry.get(family)
    if metric is None:
        return {}
    out = {}
    for labels, child in metric.items():
        labels = dict(labels)
        key = tuple(labels[n] for n in names)
        out[key[0] if len(names) == 1 else key] = child.value
    return out


def step(main, startup, feed, fetch=()):
    """One step of ``main`` on a fresh executor and scope (``startup``'s
    parameters): the program label its compile published under in the
    process's ``REGISTRY``."""
    import paddle_tpu as fluid
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=feed, scope=scope, fetch_list=list(fetch))
    exe.close()
    return f"{id(main)}:v{main._version}"
