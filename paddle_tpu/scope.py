"""Scope: hierarchical name -> value store (reference ``scope.h:39``).

Values are jax arrays (committed to device) or host numpy arrays; the
Executor moves values to/from device as needed.  Unlike the reference —
where every op reads and writes Variables in the Scope — only block
*boundaries* touch the scope here: feeds, fetches, and persistable state.
Everything intermediate lives inside the compiled XLA computation.
"""

from __future__ import annotations

__all__ = ["Scope", "global_scope", "scope_guard"]

import contextlib
import threading
import weakref


class Scope:
    def __init__(self, parent=None):
        self._vars = {}
        self.parent = parent
        self.kids = []
        # LoD metadata (row-splits per level) carried next to ragged tensors
        self._lod = {}
        # bound methods (held weakly) to be told of the next write or
        # erasure of this scope's own variables (``watch``)
        self._watchers = []
        self._watch_lock = threading.Lock()

    def new_scope(self):
        kid = Scope(parent=self)
        self.kids.append(kid)
        return kid

    def var(self, name):
        """Find-or-create (reference Scope::Var)."""
        s = self.find_scope(name)
        if s is not None:
            return s._vars[name]
        self._vars[name] = None
        self._wrote(None)
        return None

    def find_scope(self, name):
        s = self
        while s is not None:
            if name in s._vars:
                return s
            s = s.parent
        return None

    def find_var(self, name):
        s = self.find_scope(name)
        return None if s is None else s._vars[name]

    def has_var(self, name):
        return self.find_scope(name) is not None

    def set_var(self, name, value, by=None):
        """``by``: the watcher (``watch``) that makes this write itself
        and is not to be told of it."""
        s = self.find_scope(name) or self
        s._vars[name] = value
        if s._watchers:
            s._wrote(by)

    def erase(self, names):
        for n in names:
            self._vars.pop(n, None)
            self._lod.pop(n, None)
        self._wrote(None)

    def watch(self, method):
        """Call the bound ``method()`` at the NEXT ``set_var`` / ``erase``
        by anyone else that a lookup from this scope can see (its own and
        its ancestors'), so a caller that keeps resolved arrays (an
        executor's dispatch record) lets go of them the moment one is
        replaced, and not when it next looks.  Told once: whoever resolves
        again watches again, so a write costs what the watchers that hold
        something cost, not one call a watcher ever made.  Held weakly."""
        ref, s = weakref.WeakMethod(method), self
        while s is not None:
            with s._watch_lock:
                if ref not in s._watchers:
                    s._watchers.append(ref)
            s = s.parent

    def _wrote(self, by):
        with self._watch_lock:
            methods = [ref() for ref in self._watchers]
            # the writer itself stays: its own write tells it nothing
            self._watchers = [ref for ref, m in zip(self._watchers, methods)
                              if m is not None and m.__self__ is by]
        for method in methods:
            if method is not None and method.__self__ is not by:
                method()

    def local_var_names(self):
        return list(self._vars)

    def items(self):
        return list(self._vars.items())

    # -- LoD metadata ------------------------------------------------------
    def set_lod(self, name, lod):
        if lod is None:
            s = self
            while s is not None:
                s._lod.pop(name, None)
                s = s.parent
        else:
            self._lod[name] = lod

    def find_lod(self, name):
        s = self
        while s is not None:
            if name in s._lod:
                return s._lod[name]
            s = s.parent
        return None

    def drop_kids(self):
        self.kids = []


_global_scope = Scope()
_current_scope = _global_scope


def global_scope():
    return _current_scope


@contextlib.contextmanager
def scope_guard(scope):
    global _current_scope
    prev, _current_scope = _current_scope, scope
    try:
        yield
    finally:
        _current_scope = prev
