"""Architecture registry — import every config module to register it."""
from repro.configs import (h2o_danube_1_8b, qwen1_5_0_5b, gemma2_2b,  # noqa
                           llama3_8b, phi3_vision_4_2b, dbrx_132b,    # noqa
                           mixtral_8x7b, hymba_1_5b, hubert_xlarge,   # noqa
                           rwkv6_7b, mellum2_12b)                     # noqa
from repro.configs.base import ArchConfig, get, names, reduced  # noqa
from repro.configs.shapes import SHAPES, Shape, cell_supported, all_cells  # noqa
