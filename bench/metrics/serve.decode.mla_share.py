"""The share of the decode step's device time spent under the program's
`mla` named scopes: the device time of the ops whose HLO metadata
places them there (`hloscope`, from the compiled `jit_serve_decode`
module the driver keeps in `out.counts["decode_op_scopes"]`) within the
runs of `jit_serve_decode`, over those runs' busy time. None where the
trace holds no such run or the driver kept no scopes."""
import hloscope
import progtrace


def read(ctx, out, trace):
    pt = progtrace.for_outcome(out)
    scopes = out.counts.get("decode_op_scopes")
    if pt is None or not scopes:
        return None
    return hloscope.share(pt, "jit_serve_decode", scopes, "mla")
