"""Seeded lock-discipline violations (GL301/302).  Never imported."""
import threading


class BadEngine:
    def __init__(self):
        self._lock = threading.Lock()
        self._queue = []
        self._count = 0

    def _pop_locked(self):
        self._count -= 1
        return self._queue.pop()

    def _push_locked(self, item):
        self._queue.append(item)
        self._count += 1

    def good_caller(self, item):
        with self._lock:
            self._push_locked(item)
            return self._pop_locked()

    def bad_caller(self):
        return self._pop_locked()  # GL301: no lock held

    def bad_cache_caller(self):
        return self.cache.alloc(1)  # GL301: the cache's books, no lock held

    def good_cache_caller(self):
        with self._lock:
            return self.cache.alloc(1)

    def bad_writer(self):
        self._count = 0  # GL302: lock-guarded state written outside the lock
        self._queue.append("x")  # GL302: container mutation outside the lock

    def good_locked_branch(self, item):
        with self._lock:
            if item:
                self._push_locked(item)  # inside the with: fine
