"""The fault of the program that held `ec6_3_serve.read_under_encode`
out of BENCHMARK.json from PR 24 to PR 33 (PERF.md 6): a volume
server's heartbeat thread ran `Store.collect_heartbeat()`, which walked
the volume tables without the store's lock, and `_heartbeat_loop`
caught nothing; a volume deleted or mounted under its feet raised
"dictionary changed size during iteration" and the thread was gone.
PR 27 mended it (the tables are copied under the lock), `hb_errors` has
read 0 on every ledger line since, and the cell is back: this test
asks that collecting goes on surviving volumes coming and going."""

import collections
import threading
import time


def test_collecting_a_heartbeat_survives_volumes_coming_and_going(tmp_path):
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path)], ip="127.0.0.1", port=1)
    for vid in range(1, 4):
        store.add_volume(vid, collection=f"c{vid}")
    errors, beats, stop = collections.Counter(), [0], threading.Event()

    def beat():
        while not stop.is_set():
            try:
                store.collect_heartbeat()
                beats[0] += 1
            except Exception as e:  # noqa: BLE001 — counted, then shown
                errors[f"{type(e).__name__}: {e}"] += 1

    def churn():
        vid = 100
        while not stop.is_set():
            vid += 1
            store.add_volume(vid, collection="x")
            time.sleep(0.001)
            store.delete_volume(vid)

    threads = [threading.Thread(target=beat), threading.Thread(target=churn)]
    for t in threads:
        t.start()
    time.sleep(2.0)
    stop.set()
    for t in threads:
        t.join()
    store.close()
    assert beats[0] > 100
    assert not errors, dict(errors)
