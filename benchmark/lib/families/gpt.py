"""Family ``gpt``: the dense decoder, the repo's ``TransformerLM``
(``benchmark/lib/lm.py`` builds it, ``benchmark/reference/gpt.py`` is its
reference)."""

from __future__ import annotations

from benchmark.lib.lm import build_model as build  # noqa: F401
