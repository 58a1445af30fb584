"""Config for --arch lfm2-24b-a2b (see repro.configs.archs for the source dims)."""
from repro.configs.archs import lfm2_24b_a2b, lfm2_24b_a2b_smoke

full = lfm2_24b_a2b
smoke = lfm2_24b_a2b_smoke
