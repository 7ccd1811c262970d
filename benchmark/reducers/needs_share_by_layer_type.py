"""``needs_share`` over the ``fused_attention`` ops of ONE layer type, in a
model whose attention layers differ by type (window and full): the ops are
told apart by the program's own record, the configuration's ``layer_types``,
not by a name of their own. The forward ops of the train step are appended
in layer order and their grad ops in the reverse order, so among the scopes
the spec's globs match (``fused_attention#<idx>``, ``fused_attention_grad#
<idx>``) the i-th forward scope by ``idx`` and the i-th grad scope from the
end belong to the i-th attention layer. The spec's ``layer_type`` picks the
layers; the rest (``needs``, ``custom_call_target``, ``over``) is
``needs_share``'s, which does the arithmetic on the scopes picked.

None where there is no trace or no chip, and where the step does not hold
one forward and one grad scope for every attention layer of the
configuration (another program, a parent commit without the layer type).
"""
from fnmatch import fnmatchcase

from benchmark import trace as tr
from benchmark.reducers import needs_share

ATTENTION = ("full_attention", "sliding_attention", "attention")


def scopes_of(spec, ev):
    """The scopes of the attention layers of ``spec["layer_type"]``: their
    forward ops' and their grad ops'. None where the count does not fit."""
    kinds = [k for k in ev.cell["model"].get("layer_types", [])
             if k in ATTENTION]
    found = {s.scope for s in ev.hlo.values()
             if s.scope and any(fnmatchcase(s.scope, g)
                                for g in spec["match"])}
    index = lambda scope: int(scope.rsplit("#", 1)[1])          # noqa: E731
    forward = sorted((s for s in found if not tr.op_type(s).endswith("_grad")),
                     key=index)
    backward = sorted((s for s in found if tr.op_type(s).endswith("_grad")),
                      key=index, reverse=True)
    if not kinds or len(forward) != len(kinds) or len(backward) != len(kinds):
        return None
    return [s for k, pair in zip(kinds, zip(forward, backward))
            if k == spec["layer_type"] for s in pair]


def reduce(spec, ev):
    if ev.trace is None or ev.peaks is None:
        return None
    scopes = scopes_of(spec, ev)
    if not scopes:
        return None
    return needs_share.reduce(dict(spec, match=scopes), ev)
