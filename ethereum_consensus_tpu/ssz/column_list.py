"""Column-primary storage for scalar uint lists (docs/OPS_VECTOR.md,
"The storage contract").

The columnar epoch pass computes a whole registry-sized list (balances;
in a leak the inactivity scores too) as one ``uint64[n]`` array and hands
it over at commit. Until this module the list then held its content
twice: as n boxed Python ints (one ``tolist`` and one slice store a
boundary, 2^20 fresh 32-byte objects each) and as the adopted column that
every registry-sized consumer already read instead. Nobody read the
boxes.

``adopt`` makes such a list *column-primary*: its class is switched to
``ColumnList`` (same ``__slots__`` layout as ``CachedRootList``), the
array in its ``_col_cache`` record IS the content, and the list's own
slots hold n references to one sentinel that refuses arithmetic, so a raw
read that slipped past this module raises instead of serving last
epoch's values. Nothing is boxed on entry and nothing on the way through
a block:

* served from the array, staying in the mode: ``len``, ``lst[i]``,
  ``lst[i] = v`` and ``append(v)`` for a plain ``int`` the column holds
  exactly (all the marks of the instrumented mutators; the array is
  written under ``_sync_list_col``'s ownership rule: a column shared
  with a copy is cloned before its first write);
* served from the array through boxed temporaries that are not kept:
  iteration, ``in``, ``index``, ``count``, comparisons, slices,
  concatenation, ``__reduce__``; ``__array__`` hands numpy a copy of the
  column;
* everything else (the other instrumented mutators, a value the column
  cannot hold, a ``bulk_store`` aimed at the list) calls ``leave`` first:
  one ``tolist`` and one slice store, after which the list is exactly
  what ``bulk_store`` of an ndarray leaves (boxed ints plus the clean
  column) and the plain code runs, structured errors included.

A list that was never adopted shares nothing with this module: plain
``CachedRootList`` keeps C-speed reads and no flag is tested anywhere.
Which kind a list is follows from what happened to it.

Invariants of a column-primary list: ``_col_cache`` is ``("list", arr,
vmax)`` or ``("list", arr, vmax, buf)`` with ``arr`` a 1-D ``uint64``
array as long as the list (``buf``, where present, is the over-allocated
buffer ``arr`` is the head of, so that ``append`` is amortised);
``_col_dirty == set()`` always (``_clean_wire_column`` therefore holds by
construction: the root's splice and full pack take the array);
``_uniform_kind == ("int",)``.
"""

from __future__ import annotations

import operator

from ..telemetry import metrics as _metrics
from .core import CachedRootList, INSTRUMENTED_LIST_MUTATORS
from . import core as _core

__all__ = ["ColumnList", "UNBOXED", "adopt", "leave", "share"]

# adoptions that boxed nothing; times a list left the mode, and the rows
# boxed then (docs/OBSERVABILITY.md)
_STORES = _metrics.counter("ssz.column_list.stores")
_LEFT = _metrics.counter("ssz.column_list.left")
_BOXED_ROWS = _metrics.counter("ssz.column_list.boxed_rows")
# rows ``append`` grew a column by: the working columns' own count
# (models/ops_vector.py, which extends every other list's column), since
# here the content is the working column
_EXTENDED_ROWS = _metrics.counter("ops_vector.columns.extended_rows")


class _Unboxed:
    """What the slots of a column-primary list hold: one shared object
    that is no number. Anything that reaches it walked the raw storage
    (``list.__getitem__``, a C fast path) instead of the list."""

    __slots__ = ()

    def __repr__(self) -> str:
        return "<unboxed>"

    def _refuse(self, *_args):
        raise TypeError(
            "raw slot of a column-primary list: its content is the "
            "adopted column (ssz/column_list.py), read it through the list"
        )

    __int__ = __index__ = __float__ = __bool__ = __hash__ = _refuse
    __eq__ = __ne__ = __lt__ = __le__ = __gt__ = __ge__ = _refuse
    __add__ = __radd__ = __sub__ = __rsub__ = __mul__ = __rmul__ = _refuse
    __floordiv__ = __rfloordiv__ = __mod__ = __rmod__ = _refuse
    __truediv__ = __rtruediv__ = __neg__ = __pos__ = __abs__ = _refuse
    __and__ = __rand__ = __or__ = __ror__ = __xor__ = __rxor__ = _refuse
    __lshift__ = __rlshift__ = __rshift__ = __rrshift__ = _refuse


UNBOXED = _Unboxed()


def _other(value):
    """An operand as something the C list code may walk."""
    return value._boxed() if value.__class__ is ColumnList else value


class ColumnList(CachedRootList):
    """A ``CachedRootList`` whose content is the column it adopted. Never
    constructed: ``adopt`` and ``share`` switch a list's class to it and
    ``leave`` switches it back."""

    __slots__ = ()

    def _boxed(self) -> list:
        return self._col_cache[1].tolist()

    def _writable(self):
        """The array, ours to write: a column shared with a copy (or one
        that came read-only) is cloned first."""
        cc = self._col_cache
        arr = cc[1]
        if not self._col_owned:
            arr = arr.copy()
            self._col_cache = ("list", arr, cc[2])
            self._col_owned = True
        return arr

    # -- reads ---------------------------------------------------------
    def __getitem__(self, i):
        arr = self._col_cache[1]
        if type(i) is slice:
            return arr[i].tolist()
        try:
            return arr.item(operator.index(i))
        except (IndexError, OverflowError):
            raise IndexError("list index out of range") from None

    def __iter__(self):
        return iter(self._boxed())

    def __reversed__(self):
        return reversed(self._boxed())

    def __contains__(self, value) -> bool:
        return value in self._boxed()

    def index(self, *args):
        return self._boxed().index(*args)

    def count(self, value) -> int:
        return self._boxed().count(value)

    def copy(self) -> list:
        return self._boxed()

    def __repr__(self) -> str:
        return repr(self._boxed())

    def __eq__(self, other):
        return self._boxed() == _other(other)

    def __ne__(self, other):
        return self._boxed() != _other(other)

    def __lt__(self, other):
        return self._boxed() < _other(other)

    def __le__(self, other):
        return self._boxed() <= _other(other)

    def __gt__(self, other):
        return self._boxed() > _other(other)

    def __ge__(self, other):
        return self._boxed() >= _other(other)

    __hash__ = None

    def __add__(self, other):
        return self._boxed() + _other(other)

    def __radd__(self, other):
        return other + self._boxed()

    def __mul__(self, k):
        return self._boxed() * k

    __rmul__ = __mul__

    def __reduce__(self):
        return (CachedRootList, (self._boxed(),))

    def __array__(self, dtype=None, copy=None):
        arr = self._col_cache[1]
        return arr.copy() if dtype is None else arr.astype(dtype)

    # -- the two writes a block makes ----------------------------------
    def __setitem__(self, i, value):
        cc = self._col_cache
        if type(i) is not int or type(value) is not int or not (
            0 <= value <= cc[2]
        ):
            leave(self)
            return CachedRootList.__setitem__(self, i, value)
        n = cc[1].shape[0]
        if i < 0:
            i += n
        if not 0 <= i < n:
            raise IndexError("list assignment index out of range")
        _core._mark_mutated(self)
        self._writable()[i] = value
        dg = self._dirty_groups
        if dg is not None:
            dg.add(i >> _core._DIRTY_GROUP_SHIFT)
            self._dirty_elems = None

    def append(self, value) -> None:
        cc = self._col_cache
        if type(value) is not int or not 0 <= value <= cc[2]:
            leave(self)
            return CachedRootList.append(self, value)
        arr = cc[1]
        n = arr.shape[0]
        buf = cc[3] if len(cc) > 3 else None
        if buf is None or not self._col_owned or buf.shape[0] <= n:
            # amortised growth: one copy buys an eighth more rows
            import numpy as np

            buf = np.empty(n + max(n >> 3, 64), dtype=arr.dtype)
            buf[:n] = arr
            self._col_owned = True
        _core._mark_mutated(self)
        buf[n] = value
        self._col_cache = ("list", buf[: n + 1], cc[2], buf)
        list.append(self, UNBOXED)
        _EXTENDED_ROWS.inc()
        dg = self._dirty_groups
        if dg is not None:
            dg.add(n >> _core._DIRTY_GROUP_SHIFT)
            self._dirty_elems = None


def _leaving(name):
    plain = getattr(CachedRootList, name)

    def method(self, *args, **kwargs):
        leave(self)
        return plain(self, *args, **kwargs)

    method.__name__ = name
    return method


for _name in INSTRUMENTED_LIST_MUTATORS:
    if _name not in ("__setitem__", "append"):
        setattr(ColumnList, _name, _leaving(_name))
del _name


def adopt(lst, arr, changed, vmax) -> bool:
    """The column-primary store: ``arr`` (a 1-D ``uint64`` array as long
    as ``lst``, handed over by the caller) becomes the content of ``lst``
    and no row is boxed. ``changed`` names every row whose value differs
    from the list's current content: a boolean mask over the rows, or
    their indices. Marks what ``bulk_store`` marks. False, and nothing
    touched, where ``lst`` is no plain ``CachedRootList`` (or one already
    column-primary) or ``arr`` is not such a column: the caller then
    stores through ``bulk_store``."""
    cls = lst.__class__
    n = len(lst)
    if (
        (cls is not CachedRootList and cls is not ColumnList)
        or getattr(arr, "ndim", 0) != 1
        or arr.shape[0] != n
        or arr.dtype.kind != "u"
        or arr.dtype.itemsize != 8
    ):
        return False
    import numpy as np

    changed = np.asarray(changed)
    gs = _core._DIRTY_GROUP_SHIFT
    if changed.dtype.kind == "b":
        # by group straight off the comparison: no index array, no sort
        full = (n >> gs) << gs
        groups = np.flatnonzero(
            changed[:full].reshape(-1, 1 << gs).any(axis=1)
        ).tolist()
        if full < n and bool(changed[full:].any()):
            groups.append(n >> gs)
    else:
        groups = np.unique(changed.astype(np.int64) >> gs).tolist()
    if cls is CachedRootList:
        # entry: the boxed content goes, the slots take the sentinel
        list.__setitem__(lst, slice(None), [UNBOXED] * n)
        lst.__class__ = ColumnList
    _core._mark_mutated(lst)
    lst._uniform_kind = ("int",)
    dg = lst._dirty_groups
    if dg is not None:
        dg.update(groups)
        lst._dirty_elems = None  # a bulk store is known by group alone
    lst._col_cache = ("list", arr, vmax)
    lst._col_owned = bool(arr.flags.writeable)
    lst._col_dirty = set()
    _STORES.inc()
    return True


def leave(lst) -> None:
    """Box the column back into the list's slots and make it a plain
    ``CachedRootList`` again: what ``bulk_store`` of the same ndarray
    would have left (boxed ints, the clean column as its cache). No mark
    moves: the content is the same."""
    if lst.__class__ is not ColumnList:
        return
    boxed = lst._col_cache[1].tolist()
    lst.__class__ = CachedRootList
    list.__setitem__(lst, slice(None), boxed)
    _LEFT.inc()
    _BOXED_ROWS.inc(len(boxed))


def share(value: ColumnList) -> ColumnList:
    """``_copy_value`` of a column-primary list: a column-primary list of
    the same length that boxes nothing. The caller shares the column
    record (``_share_col_cache``: the same array, ownership dropped on
    both sides, so whichever side writes first clones) and the root
    memos, as it does for every ``CachedRootList``."""
    copied = CachedRootList([UNBOXED] * len(value))
    copied.__class__ = ColumnList
    return copied
