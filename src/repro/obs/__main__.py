"""``python -m repro.obs`` — see :mod:`repro.obs.cli`."""

import sys

from ..compile_cache import enable_compile_cache
from .cli import main

enable_compile_cache()
sys.exit(main())
