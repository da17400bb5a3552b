from jimm_tpu.train.checkpoint import CheckpointManager
from jimm_tpu.train.losses import (blocked_cross_entropy, clip_softmax_loss,
                                   exit_distribution, expected_exit_loss,
                                   ring_clip_infonce_loss, ring_sigmoid_loss,
                                   sigmoid_pairwise_loss)
from jimm_tpu.train.metrics import (MetricsLogger, StepTimer, compiled_flops,
                                    device_peak_tflops, mfu)
from jimm_tpu.train.trainer import (OptimizerConfig, contrastive_loss_fn,
                                    lm_loss_fn, make_classifier_eval_step,
                                    make_classifier_train_step,
                                    make_contrastive_train_step,
                                    make_lm_train_step, make_optimizer,
                                    make_schedule)

__all__ = [
    "CheckpointManager", "MetricsLogger", "StepTimer", "OptimizerConfig",
    "clip_softmax_loss", "sigmoid_pairwise_loss", "ring_sigmoid_loss",
    "ring_clip_infonce_loss",
    "blocked_cross_entropy", "exit_distribution", "expected_exit_loss",
    "lm_loss_fn", "make_lm_train_step",
    "contrastive_loss_fn", "make_classifier_train_step",
    "make_classifier_eval_step", "make_contrastive_train_step",
    "make_optimizer", "make_schedule", "compiled_flops", "device_peak_tflops",
    "mfu",
]
