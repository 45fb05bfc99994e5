"""One module per arrival process, ``bench/loops/<kind>.py``, found by
the ``kind`` of a traffic mix.  Each has ``run(client)``: it drives
``client`` (a ``bench.drive.Client``) until the window's end mark."""
