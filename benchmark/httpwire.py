"""One keep-alive HTTP connection of a REST client: the REST traffic
kinds send through it."""

from __future__ import annotations

import http.client


class HttpClient:
    def __init__(self, address, timeout: float):
        self.address, self.timeout = address, timeout
        self.conn = None

    def request(self, method: str, path: str, body: bytes | None = None):
        """(status, body), or None where the connection failed (it is
        opened anew for the next request)."""
        try:
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    *self.address, timeout=self.timeout)
            self.conn.request(
                method, path, body=body,
                headers={"Content-Type": "application/json"} if body else {})
            resp = self.conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException):
            self.close()
            return None

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None
