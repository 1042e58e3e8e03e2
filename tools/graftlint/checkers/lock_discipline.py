"""Checker: engine lock discipline (GL3xx).

Invariant (paged-engine convention since PR 2): a ``_*_locked`` helper
encodes "caller holds the lock" in its NAME — it must only be invoked
from another ``_*_locked`` method or lexically inside a ``with
self.<lock>:`` block of the same class.  Conversely, mutable state that
``_*_locked`` methods write is lock-guarded by definition, so writes to
those attributes from unlocked contexts are flagged.

Rules:

* GL301 — ``self._x_locked(...)`` called from a method that is neither
  itself ``*_locked`` nor inside a ``with self.<lock>`` block.  The
  paged engine's allocator and prefix index live in ``self.cache``
  (``models/paged/cache.py PagedCache``, which takes no lock of its
  own: the caller holds the engine's), so ``self.cache.<books>(...)``
  is held to the same rule.
* GL302 — write to a lock-guarded ``self.<attr>`` (one that some
  ``*_locked`` method of the class also writes) outside lock scope
  (``__init__``/``__new__`` construct before the object escapes and
  are exempt).
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from tools.graftlint.core import LintContext, Source, Violation

NAME = "lock-discipline"

# a `with self.<attr>:` item counts as taking the lock when the attr
# looks like one
_LOCK_HINTS = ("lock", "mutex", "_cv", "_mu", "cond")


# ``PagedCache`` methods that read or write the books the engine's lock
# guards (free lists, reference counts, tables, the prefix index)
_CACHE_BOOKS = frozenset((
    "alloc", "free", "allocatable", "evict_cached", "seat", "ensure_pages",
    "window_ensure", "free_window", "release", "match_prefix", "map_prefix",
    "unmap_prefix", "register_prefix", "check_invariants",
))


def _locked_callee(call: ast.Call) -> Optional[str]:
    """The callee's name where ``call`` is one the lock must cover:
    ``self._x_locked(...)`` or ``self.cache.<books>(...)``."""
    func = call.func
    if not isinstance(func, ast.Attribute):
        return None
    owner = func.value
    if isinstance(owner, ast.Name) and owner.id == "self" \
            and func.attr.endswith("_locked"):
        return func.attr
    if isinstance(owner, ast.Attribute) and owner.attr == "cache" \
            and isinstance(owner.value, ast.Name) and owner.value.id == "self" \
            and func.attr in _CACHE_BOOKS:
        return f"cache.{func.attr}"
    return None


def _is_lock_attr(attr: str) -> bool:
    a = attr.lower()
    return any(h in a for h in _LOCK_HINTS)


def _with_takes_lock(node: ast.AST) -> bool:
    if not isinstance(node, (ast.With, ast.AsyncWith)):
        return False
    for item in node.items:
        expr = item.context_expr
        # with self._lock:  /  with self._cv:
        if isinstance(expr, ast.Attribute) and _is_lock_attr(expr.attr) \
                and isinstance(expr.value, ast.Name) and expr.value.id == "self":
            return True
        # with self._lock.something(): (e.g. cv timeouts) — still the lock
        if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Attribute):
            v = expr.func.value
            if isinstance(v, ast.Attribute) and _is_lock_attr(v.attr) \
                    and isinstance(v.value, ast.Name) and v.value.id == "self":
                return True
    return False


class _Checker:
    name = NAME
    codes = ("GL301", "GL302")
    doc = __doc__

    def run(self, ctx: LintContext) -> Iterable[Violation]:
        out: List[Violation] = []
        for src in ctx.sources:
            out.extend(self.check_source(src))
        return out

    def check_source(self, src: Source) -> List[Violation]:
        out: List[Violation] = []
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ClassDef):
                out.extend(self._check_class(src, node))
        return out

    def _check_class(self, src: Source, cls: ast.ClassDef) -> List[Violation]:
        methods = [
            n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        locked_methods = {m.name for m in methods if m.name.endswith("_locked")}
        if not locked_methods:
            return []

        # attrs WRITTEN by *_locked methods = lock-guarded state
        guarded: Set[str] = set()
        for m in methods:
            if m.name in locked_methods:
                guarded |= self._self_writes(m)

        out: List[Violation] = []
        for m in methods:
            holds_by_name = m.name.endswith("_locked")
            exempt_init = m.name in ("__init__", "__new__")
            self._walk(
                src, cls, m, m.body, in_lock=holds_by_name,
                guarded=guarded, exempt_writes=exempt_init or holds_by_name,
                out=out,
            )
        return out

    def _walk(self, src: Source, cls: ast.ClassDef, method,
              body, in_lock: bool, guarded: Set[str],
              exempt_writes: bool, out: List[Violation]) -> None:
        for node in body:
            locked_here = in_lock or _with_takes_lock(node)
            # GL301: self.*_locked(...) calls
            for sub in self._shallow_walk(node):
                callee = (_locked_callee(sub)
                          if isinstance(sub, ast.Call) and not locked_here
                          else None)
                if callee is not None:
                    out.append(Violation(
                        checker=self.name, code="GL301", path=src.path,
                        line=sub.lineno,
                        symbol=f"{cls.name}.{method.name}->{callee}",
                        message=(
                            f"self.{callee}() called from "
                            f"{cls.name}.{method.name} without holding the "
                            "lock (not a *_locked method, not inside "
                            "`with self.<lock>:`)"
                        ),
                    ))
                # GL302: unlocked writes to guarded attrs
                if not locked_here and not exempt_writes:
                    attr = self._write_target(sub)
                    if attr is not None and attr in guarded:
                        out.append(Violation(
                            checker=self.name, code="GL302", path=src.path,
                            line=sub.lineno,
                            symbol=f"{cls.name}.{method.name}.{attr}",
                            message=(
                                f"self.{attr} is written by *_locked methods "
                                f"(lock-guarded state) but {cls.name}."
                                f"{method.name} writes it outside lock scope"
                            ),
                        ))
            # recurse, tracking lock scope lexically
            children = getattr(node, "body", None)
            if children:
                self._walk(src, cls, method, children, locked_here,
                           guarded, exempt_writes, out)
            for extra in ("orelse", "finalbody", "handlers"):
                sub_body = getattr(node, extra, None)
                if sub_body:
                    items = []
                    for h in sub_body:
                        if isinstance(h, ast.ExceptHandler):
                            items.extend(h.body)
                        else:
                            items.append(h)
                    self._walk(src, cls, method, items, locked_here,
                               guarded, exempt_writes, out)

    @staticmethod
    def _shallow_walk(node: ast.AST):
        """Yield the statement node's expressions without descending
        into nested statements (those are handled by _walk so lock
        scope stays lexical)."""
        if isinstance(node, (ast.With, ast.AsyncWith, ast.If, ast.For,
                             ast.AsyncFor, ast.While, ast.Try)):
            # header expressions only
            for field in ("items", "test", "iter", "target"):
                val = getattr(node, field, None)
                if val is None:
                    continue
                vals = val if isinstance(val, list) else [val]
                for v in vals:
                    expr = getattr(v, "context_expr", v)
                    yield from ast.walk(expr)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            return  # nested defs run later, in their own context
        else:
            yield from ast.walk(node)

    @staticmethod
    def _self_writes(method) -> Set[str]:
        """Names of self attributes this method assigns/augments/
        subscript-writes."""
        out: Set[str] = set()
        for node in ast.walk(method):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                # self.attr = ... | self.attr[k] = ...
                if isinstance(t, ast.Subscript):
                    t = t.value
                if isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self":
                    out.add(t.attr)
            # self.attr.append/extend/update/clear(...): mutation too
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("append", "extend", "update",
                                           "clear", "pop", "popleft",
                                           "appendleft", "add", "remove",
                                           "discard", "setdefault"):
                v = node.func.value
                if isinstance(v, ast.Attribute) \
                        and isinstance(v.value, ast.Name) and v.value.id == "self":
                    out.add(v.attr)
        return out

    @staticmethod
    def _write_target(node: ast.AST) -> Optional[str]:
        """The self-attribute a statement-level node writes, if any."""
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("append", "extend", "update", "clear",
                                       "pop", "popleft", "appendleft", "add",
                                       "remove", "discard", "setdefault"):
            v = node.func.value
            if isinstance(v, ast.Attribute) \
                    and isinstance(v.value, ast.Name) and v.value.id == "self":
                return v.attr
        for t in targets:
            if isinstance(t, ast.Subscript):
                t = t.value
            if isinstance(t, ast.Attribute) \
                    and isinstance(t.value, ast.Name) and t.value.id == "self":
                return t.attr
        return None


CHECKER = _Checker()
