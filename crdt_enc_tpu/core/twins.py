"""Sync twins of the plugin ports: what a worker-thread job may call.

A port method is awaitable; some backends can also do the same work as a
plain function (``Storage.store_state`` / ``store_state_sync``,
``Cryptor.encrypt`` / ``encrypt_fn``).  The seal tail runs as ONE worker
job when both ports offer such twins for every call it makes, and
awaits each port call on the loop when either does not
(``Core._compact_seal``).  Twins are optional: a plugin without them
loses nothing but the shortcut.

:func:`offers` is the one place that decides.  It looks at the port's
CLASS, not the instance, and demands that no awaitable be defined
further down the class hierarchy than its twin:

* a wrapper that forwards unknown attributes to an inner storage
  (``__getattr__``: the simulator's tap, the daemon selftest's flaky
  remote) offers nothing it does not define itself, so its own
  awaitables, the ones that inject the fault or record the write, stay
  on the path;
* a subclass that overrides the awaitable alone (a test's checking
  cryptor over ``IdentityCryptor``) has left the inherited twin behind,
  and is treated as having none.  One that overrides the twin alone is
  in step by construction: the inherited awaitable is written over it.
"""

from __future__ import annotations


def _provider(cls: type, attr: str) -> type | None:
    for c in cls.__mro__:
        if attr in vars(c):
            return c
    return None


def offers(port, pairs) -> bool:
    """True when ``port``'s class provides every ``(awaitable, twin)``
    name pair, each twin by the class that provides its awaitable or
    by a subclass of it."""
    cls = type(port)
    for name, twin in pairs:
        by, shadowed = _provider(cls, twin), _provider(cls, name)
        if by is None or shadowed is None or not issubclass(by, shadowed):
            return False
    return True
