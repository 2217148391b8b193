"""Import side-effect module: loads every per-arch config file so the
registry in ``repro.configs.base`` is populated."""
import repro.configs.yi_34b  # noqa: F401
import repro.configs.qwen2_0_5b  # noqa: F401
import repro.configs.mistral_large_123b  # noqa: F401
import repro.configs.qwen3_1_7b  # noqa: F401
import repro.configs.granite_moe_3b_a800m  # noqa: F401
import repro.configs.mixtral_8x22b  # noqa: F401
import repro.configs.mamba2_780m  # noqa: F401
import repro.configs.phi_3_vision_4_2b  # noqa: F401
import repro.configs.whisper_large_v3  # noqa: F401
import repro.configs.hymba_1_5b  # noqa: F401
import repro.configs.granite_4_0_h_micro  # noqa: F401
