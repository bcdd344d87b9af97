"""Faults planted in the timed path, to show that the check fails them.

Each takes ``patch(owner, name, value)`` (``setattr``, or a test's
``monkeypatch.setattr``) and breaks the program's ``ServingEngine`` under a
whole run. The benchmark's own runs never plant one; ``control.py --fault``
and the tests do.
"""
from __future__ import annotations


def altered_token(patch) -> None:
    """Every fifth token a request emits is replaced where it is made."""
    from repro.serving.engine import ServingEngine
    emit = ServingEngine._emit_token

    def wrong(self, i, r, tok, now):
        if len(r.generated) % 5 == 4:
            tok = (tok + 1) % self.cfg.vocab_size
        return emit(self, i, r, tok, now)
    patch(ServingEngine, "_emit_token", wrong)


def unchanged_cache(patch) -> None:
    """The decode step returns the cache it was given, unwritten."""
    from repro.serving.engine import ServingEngine
    init = ServingEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        decode = self._decode
        self._decode = lambda p, c, t, pos: (decode(p, c, t, pos)[0], c)
    patch(ServingEngine, "__init__", patched)


FAULTS = {"altered_token": altered_token, "unchanged_cache": unchanged_cache}
