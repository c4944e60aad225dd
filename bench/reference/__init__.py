"""The plain references the benchmark compares with.

``resnet.FORWARD`` maps a configuration's ``model`` to its forward.  The
``resnet50`` entry is the forward of ``resnet50.py``, which the
``resnet50-tinyimagenet`` configuration brings: it trains under
``fedround``'s jitted local pass, where ``resnet.resnet50`` cannot (see
``resnet50.py``).
"""
from bench.reference import resnet, resnet50

resnet.FORWARD["resnet50"] = resnet50.resnet50
