"""Data of the ``job-star-p32`` configuration: ``job-star``'s tables, with
``p`` stored in the precision the configuration states (float32)."""
from __future__ import annotations

import os

from bench.harness import load_module

generate = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "job-star.py")).generate
