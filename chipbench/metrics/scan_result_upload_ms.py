"""Query engine: the upload of a scan query's host result to the device
(the engine's ``query.result_upload`` span around
``jnp.asarray(out_np)``), per query, in ms.  The reader of every
``scan_result_upload_ms.<regime>`` metric."""


def read(run):
    spans = [s.duration_s for s in run.spans
             if s.name == "query.result_upload"]
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
