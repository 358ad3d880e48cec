"""Import side-effect module: registers every assigned architecture."""
import repro.configs.qwen2_moe_a2_7b   # noqa: F401
import repro.configs.arctic_480b       # noqa: F401
import repro.configs.granite_8b        # noqa: F401
import repro.configs.tinyllama_1_1b    # noqa: F401
import repro.configs.qwen3_32b         # noqa: F401
import repro.configs.mistral_nemo_12b  # noqa: F401
import repro.configs.zamba2_2_7b       # noqa: F401
import repro.configs.qwen2_vl_7b       # noqa: F401
import repro.configs.xlstm_350m        # noqa: F401
import repro.configs.seamless_m4t_medium  # noqa: F401
import repro.configs.moonlight_16b_a3b  # noqa: F401
