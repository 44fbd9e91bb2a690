"""Path access into nested param dicts, used by Algorithm-1 aggregation
and the engine's split views."""
from __future__ import annotations


def get_subtree(tree, path):
    node = tree
    for p in path:
        node = node[p]
    return node


def set_subtree(tree, path, value):
    """Functional set: returns a copy of `tree` with tree[path] = value."""
    if not path:
        return value
    head, rest = path[0], path[1:]
    if isinstance(tree, dict):
        out = dict(tree)
        out[head] = set_subtree(tree[head], rest, value)
        return out
    if isinstance(tree, (list, tuple)):
        out = list(tree)
        out[head] = set_subtree(tree[head], rest, value)
        return type(tree)(out) if isinstance(tree, tuple) else out
    raise TypeError(type(tree))
