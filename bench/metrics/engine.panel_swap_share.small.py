"""The column-swap share of ``engine.panel_swap_share.py``, read in the small-N cells, where it
moves ``small_logdet_s``."""
from pathlib import Path

import registry

read = registry.load_module(Path(__file__).with_name("engine.panel_swap_share.py")).read
