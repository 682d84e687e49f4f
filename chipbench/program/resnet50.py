"""The program's ResNet-50 (bluefog_tpu.models) at the configuration's sizes."""

from bluefog_tpu import models
from bluefog_tpu.models.resnet import BottleneckBlock


def build(sizes):
    """Returns what a job kind needs to drive the model: the apply function,
    whether it carries batch statistics, and the shape of one rank's input."""
    if sizes["stage_sizes"] == [3, 4, 6, 3] and sizes["num_filters"] == 64:
        model = models.ResNet50(num_classes=sizes["num_classes"])
    else:  # the rehearsal's tiny bottleneck net: same blocks, fewer of them
        model = models.ResNet(
            stage_sizes=sizes["stage_sizes"], block_cls=BottleneckBlock,
            num_classes=sizes["num_classes"], num_filters=sizes["num_filters"])
    return {"apply_fn": model.apply, "has_batch_stats": True, "model": model}
