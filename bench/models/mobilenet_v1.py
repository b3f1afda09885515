"""The program's MobileNet v1 graph for a configuration file."""


def graph(cfg: dict):
    """``zoo.mobilenet_v1`` at the configuration's width multiplier,
    resolution and tier (int8 or float32 arena)."""
    from repro.core import zoo
    if int(cfg["classes"]) != 1000:
        raise ValueError("the program's MobileNet v1 head has 1000 classes")
    return zoo.mobilenet_v1(float(cfg["alpha"]), int(cfg["resolution"]),
                            {"int8": 1, "f32": 4}[cfg["dtype"]])
