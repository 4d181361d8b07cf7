"""Central `REPRO_*` environment configuration — resolved ONCE at import.

Every runtime knob the serving stack reads from the environment lives
here, so mesh/backend/cache configuration has a single source of truth
(and a single place to audit).  Traced code must never read
``os.environ`` per call: the trace bakes in whatever the first call saw
and the host-side dict lookup is pure overhead — resolving at import
makes that contract structural.

Import-light on purpose (stdlib only): kernels, kvstore, models and the
session all import this at module scope.

Knobs:

``REPRO_KV_CACHE``      serving KV cache default ("auto" -> paged for
                        attention archs; "full"/"paged" force it)
``REPRO_KV_DTYPE``      paged-pool value dtype ("bf16" exact / "int8")
``REPRO_KV_UPDATE``     dense-cache update strategy ("scatter"/"dynamic")
``REPRO_AUTOTUNE``      "0"/"false" disables the kernel autotuner
``REPRO_TUNE_BLOCK_ROWS``  "1" enables encode-time block_rows search
``REPRO_BF16_PSUM``     "1" narrows TP matmul partial sums to bf16
"""
from __future__ import annotations

import os

KV_CACHE: str = os.environ.get("REPRO_KV_CACHE", "auto")
KV_DTYPE: str = os.environ.get("REPRO_KV_DTYPE", "bf16")
KV_UPDATE: str = os.environ.get("REPRO_KV_UPDATE", "scatter")
AUTOTUNE: bool = os.environ.get("REPRO_AUTOTUNE", "1") not in ("0", "false")
TUNE_BLOCK_ROWS: bool = os.environ.get("REPRO_TUNE_BLOCK_ROWS") == "1"
BF16_PSUM: bool = os.environ.get("REPRO_BF16_PSUM") == "1"
