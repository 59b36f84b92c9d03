"""redis-py conformance against the YEDIS server.

Unlike the psycopg/cassandra suites (skip-if-absent — those drivers
cannot be vendored), this one always runs: when no system redis-py is
installed it falls back to the vendored RESP2 client in
third_party/redispy (an API-compatible subset; see its docstring), so
the external-client tier executes in the default tier-1 run."""
import os
import sys

from tests.driver_cluster import ClusterThread

try:
    import redis
except ImportError:                      # vendored fallback
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "third_party", "redispy"))
    import redis


def test_redis_py_basic(tmp_path):
    from yugabyte_db_tpu.ql.redis_server import RedisServer
    with ClusterThread(tmp_path, RedisServer) as ct:
        host, port = ct.addr
        r = redis.Redis(host=host, port=port, socket_timeout=20)
        assert r.ping()
        r.set("k1", "v1")
        assert r.get("k1") == b"v1"
        assert r.incr("cnt") == 1
        assert r.incr("cnt") == 2
        r.hset("h", "f", "x")
        assert r.hget("h", "f") == b"x"
        r.rpush("l", "a", "b")
        assert r.lrange("l", 0, -1) == [b"a", b"b"]
        r.sadd("s", "m1", "m2")
        assert r.sismember("s", "m1")
        assert r.delete("k1") == 1
        assert r.get("k1") is None


def test_redis_py_wider_surface(tmp_path):
    """Exercise the rest of the vendored client's command map against
    the server: string ops, hash maps, list mutation, set cardinality
    — the same breadth tests/test_redis_breadth.py drives over the raw
    wire, here through the driver API."""
    from yugabyte_db_tpu.ql.redis_server import RedisServer
    with ClusterThread(tmp_path, RedisServer) as ct:
        host, port = ct.addr
        r = redis.Redis(host=host, port=port, socket_timeout=20)
        assert r.append("a", "foo") == 3
        assert r.append("a", "bar") == 6
        assert r.strlen("a") == 6
        assert r.exists("a") == 1
        r.hset("h2", mapping={"x": "1", "y": "2"})
        assert r.hgetall("h2") == {b"x": b"1", b"y": b"2"}
        assert r.hdel("h2", "x") == 1
        r.rpush("l2", "a", "b", "c")
        assert r.llen("l2") == 3
        assert r.lpop("l2") == b"a"
        assert r.rpop("l2") == b"c"
        r.sadd("s2", "m1", "m2", "m2")
        assert r.scard("s2") == 2
        assert r.srem("s2", "m1") == 1
        assert not r.sismember("s2", "m1")