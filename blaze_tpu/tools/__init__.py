"""Operator-facing CLI tools (``python -m blaze_tpu.tools.<name>``).

* ``sentinel`` — regression sentinel: diff two saved /history/rollup
                 payloads with noise-floor thresholds (CI exit codes).
* ``top``      — live table of running queries from /progress.
"""
