from repro.launch.train_sim import (TPU_V5E, ChipConstants, LayerProfile,
                                    TrainingRunResult, derive_layer_profiles,
                                    make_fabric, simulate_training_run,
                                    sweep_training_runs)

__all__ = [
    "TPU_V5E",
    "ChipConstants",
    "LayerProfile",
    "TrainingRunResult",
    "derive_layer_profiles",
    "make_fabric",
    "simulate_training_run",
    "sweep_training_runs",
]
