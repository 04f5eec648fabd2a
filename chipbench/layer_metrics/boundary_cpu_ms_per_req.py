"""Mean CPU time a request spends on the host side of the device
boundary: self CPU of `setop.pad` (segment split, pow2 bucketing, the
padded stack), `setop.upload` (the uploads and the DeviceCache inserts
that follow them), `setop.launch`, `setop.split`, of `vec.plan`,
`vec.launch`, `vec.post`, and of a value column's `valcol.pad`,
`valcol.upload`, `valcol.launch`. The waits are not in it
(`device_wait_ms_per_req`). 0.0 where every op stayed under the device
threshold. Layer: device boundary. Moves: qps."""

from chipbench import spans

NAMES = ("setop.pad", "setop.upload", "setop.launch", "setop.split",
         "vec.plan", "vec.launch", "vec.post",
         "valcol.pad", "valcol.upload", "valcol.launch")


def read(ctx):
    return spans.mean_self_cpu(ctx, NAMES)
