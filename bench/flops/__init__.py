"""Operations and bytes computed from shapes: the model forwards
(``resnet``) and the calls of each Pallas kernel (``grouped_conv``,
``kd_kl``).  The per-layer metrics divide these by measured time."""
