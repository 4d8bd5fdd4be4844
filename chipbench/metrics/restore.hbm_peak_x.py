"""Polled ``bytes_in_use`` max on the fullest device ÷ that device's bytes of
state under the restore layout.  A 20 ms sampler: it can miss a spike."""


def read(ctx):
    held = ctx.notes.get("restored_fullest_device_bytes")
    if not ctx.hbm_poll_max or not held:
        return None
    return ctx.hbm_poll_max / held
