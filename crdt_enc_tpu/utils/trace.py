"""Compat shim: the tracing core was promoted into the first-class
observability subsystem at :mod:`crdt_enc_tpu.obs.record` (ISSUE 2) —
timelines live in ``obs.timeline``, the runtime's signals in
``obs.runtime``, the metrics sink in ``obs.sink``.

Every existing import site (``from crdt_enc_tpu.utils import trace``)
keeps working unchanged: this module replaces itself in ``sys.modules``
with the real registry module, so module-level state — including the
``trace.jax_annotations`` flag — is THE one registry, not a copy (a
re-export shim would silently fork mutable flags set through this name).
"""

import sys

from ..obs import record as _record

sys.modules[__name__] = _record
