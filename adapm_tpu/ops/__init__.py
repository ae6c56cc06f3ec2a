"""Hot-path device programs: fused gather->grad->AdaGrad->scatter steps."""
from .fused import (DeviceRoutedRunner, DeviceRouter, StagedKeys,  # noqa
                    make_device_routed_scan, make_device_routed_score,
                    make_device_routed_step)
