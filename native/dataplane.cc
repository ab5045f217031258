// Data-plane implementation. See dataplane.h for the architecture.
#include "dataplane.h"

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/un.h>
#include <unistd.h>

#include <algorithm>
#include <cctype>
#include <cerrno>
#include <cmath>
#include <cstdlib>
#include <chrono>
#include <cstring>
#include <random>

namespace atpu {

static double now_s() {
  return std::chrono::duration<double>(
             std::chrono::system_clock::now().time_since_epoch())
      .count();
}

static long long now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::system_clock::now().time_since_epoch())
      .count();
}

static double mono_s() {
  return std::chrono::duration<double>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// ---- small utils -----------------------------------------------------------

static std::string lower(std::string s) {
  std::transform(s.begin(), s.end(), s.begin(),
                 [](unsigned char c) { return std::tolower(c); });
  return s;
}

static bool is_hop_by_hop(const std::string& lname) {
  // parity with server/app.py _HOP_BY_HOP
  static const std::set<std::string> hop = {
      "connection", "keep-alive", "proxy-authenticate", "proxy-authorization",
      "te",         "trailers",   "transfer-encoding",  "upgrade",
      "host",       "content-length", "content-encoding"};
  return hop.count(lname) > 0;
}

static std::string uuid4() {
  static thread_local std::mt19937_64 rng{std::random_device{}()};
  uint64_t hi = rng(), lo = rng();
  unsigned char b[16];
  std::memcpy(b, &hi, 8);
  std::memcpy(b + 8, &lo, 8);
  b[6] = (b[6] & 0x0f) | 0x40;  // version 4
  b[8] = (b[8] & 0x3f) | 0x80;  // variant
  char out[37];
  std::snprintf(out, sizeof(out),
                "%02x%02x%02x%02x-%02x%02x-%02x%02x-%02x%02x-"
                "%02x%02x%02x%02x%02x%02x",
                b[0], b[1], b[2], b[3], b[4], b[5], b[6], b[7], b[8], b[9], b[10],
                b[11], b[12], b[13], b[14], b[15]);
  return std::string(out, 36);
}

// ---- buffered socket I/O + HTTP parsing ------------------------------------

struct HttpMsg {
  // request fields
  std::string method, target, version;
  // response fields
  int status = 0;
  // shared
  std::vector<std::pair<std::string, std::string>> headers;  // original case
  std::string body;
  bool keepalive = true;

  std::string header(const std::string& lname) const {
    for (const auto& kv : headers)
      if (lower(kv.first) == lname) return kv.second;
    return "";
  }
};

struct SockBuf {
  int fd;
  std::string buf;
  bool timed_out = false;  // the last failed fill() ran out SO_RCVTIMEO
  explicit SockBuf(int f) : fd(f) {}

  // Returns false on EOF/error before any progress could complete.
  bool fill() {
    char chunk[1 << 14];
    ssize_t n = ::recv(fd, chunk, sizeof(chunk), 0);
    if (n <= 0) {
      timed_out = n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK);
      return false;
    }
    buf.append(chunk, static_cast<size_t>(n));
    return true;
  }

  bool read_exact(size_t n, std::string* out) {
    while (buf.size() < n)
      if (!fill()) return false;
    out->assign(buf.data(), n);
    buf.erase(0, n);
    return true;
  }

  // Read through the next CRLF; returns the line without CRLF.
  bool read_line(std::string* out) {
    size_t pos;
    while ((pos = buf.find("\r\n")) == std::string::npos) {
      if (buf.size() > (1 << 20)) return false;  // header flood guard
      if (!fill()) return false;
    }
    out->assign(buf.data(), pos);
    buf.erase(0, pos + 2);
    return true;
  }
};

static bool send_all(int fd, const char* data, size_t len) {
  while (len) {
    ssize_t n = ::send(fd, data, len, MSG_NOSIGNAL);
    if (n <= 0) return false;
    data += n;
    len -= static_cast<size_t>(n);
  }
  return true;
}

static bool send_all(int fd, const std::string& s) {
  return send_all(fd, s.data(), s.size());
}

// Parse one HTTP message from the socket. is_response selects status-line vs
// request-line. Handles Content-Length and chunked bodies. `eof_clean`
// reports EOF-before-first-byte, which on a reused upstream connection means
// a stale keepalive, not a crash; a receive timeout is not an EOF (the peer
// holds the connection and has not answered yet). `response_to_head`: HEAD
// responses carry Content-Length but no body (RFC 9110 §6.4.1), so body reads
// must be skipped.
static bool read_http(SockBuf& sb, bool is_response, HttpMsg* msg,
                      bool* eof_clean = nullptr, bool response_to_head = false) {
  static const long long MAX_BODY = 1LL << 31;  // shared CL/chunked cap
  if (eof_clean) *eof_clean = false;
  std::string line;
  if (sb.buf.empty() && eof_clean) {
    if (!sb.fill()) {
      *eof_clean = !sb.timed_out;
      return false;
    }
  }
  // interim 1xx responses precede the real one: parse-and-discard (bounded)
  for (int interim = 0; interim < 4; interim++) {
    if (!sb.read_line(&line)) return false;
    msg->headers.clear();
    msg->body.clear();
    if (is_response) {
      // HTTP/1.1 200 OK
      if (line.size() < 12 || line.compare(0, 5, "HTTP/") != 0) return false;
      msg->status = std::atoi(line.c_str() + 9);
      msg->version = line.substr(0, 8);
    } else {
      size_t sp1 = line.find(' ');
      size_t sp2 = line.rfind(' ');
      if (sp1 == std::string::npos || sp2 == sp1) return false;
      msg->method = line.substr(0, sp1);
      msg->target = line.substr(sp1 + 1, sp2 - sp1 - 1);
      msg->version = line.substr(sp2 + 1);
    }
    // headers
    for (;;) {
      if (!sb.read_line(&line)) return false;
      if (line.empty()) break;
      size_t colon = line.find(':');
      if (colon == std::string::npos) continue;
      std::string name = line.substr(0, colon);
      size_t vstart = colon + 1;
      while (vstart < line.size() && line[vstart] == ' ') vstart++;
      msg->headers.emplace_back(name, line.substr(vstart));
    }
    if (is_response && msg->status >= 100 && msg->status < 200)
      continue;  // 1xx carries no body; the real response follows
    break;
  }
  if (is_response && msg->status >= 100 && msg->status < 200)
    return false;  // 1xx flood
  std::string conn = lower(msg->header("connection"));
  msg->keepalive = (msg->version == "HTTP/1.1") ? conn != "close" : conn == "keep-alive";
  // bodyless responses: HEAD answers, 204, 304 (RFC 9110 §6.4.1)
  if (is_response &&
      (response_to_head || msg->status == 204 || msg->status == 304))
    return true;
  std::string te = lower(msg->header("transfer-encoding"));
  if (!te.empty() && te != "identity") {
    // chunked body decode (requests and responses)
    for (;;) {
      if (!sb.read_line(&line)) return false;
      // strict hex chunk size: >=1 hex digit, then end or ';' (extensions)
      char* endp = nullptr;
      errno = 0;
      long long sz = std::strtoll(line.c_str(), &endp, 16);
      if (endp == line.c_str() || errno == ERANGE || sz < 0) return false;
      if (*endp != '\0' && *endp != ';' && *endp != ' ' && *endp != '\r')
        return false;
      if (sz == 0) {
        // trailers until blank line
        while (sb.read_line(&line) && !line.empty()) {
        }
        break;
      }
      if (sz > MAX_BODY ||
          static_cast<long long>(msg->body.size()) + sz > MAX_BODY)
        return false;
      std::string chunk;
      if (!sb.read_exact(static_cast<size_t>(sz), &chunk)) return false;
      msg->body += chunk;
      if (!sb.read_line(&line)) return false;  // trailing CRLF
    }
    return true;
  }
  std::string cl = msg->header("content-length");
  if (!cl.empty()) {
    long long n = std::strtoll(cl.c_str(), nullptr, 10);
    if (n < 0 || n > MAX_BODY) return false;
    if (n > 0 && !sb.read_exact(static_cast<size_t>(n), &msg->body)) return false;
  }
  return true;
}

static std::string status_reason(int code) {
  switch (code) {
    case 200: return "OK";
    case 202: return "Accepted";
    case 400: return "Bad Request";
    case 401: return "Unauthorized";
    case 404: return "Not Found";
    case 502: return "Bad Gateway";
    case 503: return "Service Unavailable";
    case 504: return "Gateway Timeout";
    default: return "Status";
  }
}

// `cl_override`: a HEAD response's Content-Length must advertise the size the
// corresponding GET would have (RFC 9110 §9.3.2) even though no body is sent;
// pass the upstream's Content-Length header value there, else "" stamps
// body.size().
static std::string build_response(int code,
                                  const std::vector<std::pair<std::string, std::string>>& headers,
                                  const std::string& body, bool keepalive,
                                  const std::string& cl_override = "") {
  std::string out = "HTTP/1.1 " + std::to_string(code) + " " + status_reason(code) + "\r\n";
  bool have_ct = false;
  for (const auto& kv : headers) {
    std::string l = lower(kv.first);
    if (is_hop_by_hop(l)) continue;
    if (l == "content-type") have_ct = true;
    out += kv.first + ": " + kv.second + "\r\n";
  }
  if (!have_ct) out += "Content-Type: application/json\r\n";
  out += "Content-Length: " +
         (cl_override.empty() ? std::to_string(body.size()) : cl_override) + "\r\n";
  out += keepalive ? "Connection: keep-alive\r\n" : "Connection: close\r\n";
  out += "\r\n";
  out += body;
  return out;
}

// {"success":..,"message":..,"data":..} envelope (server.go:50-54 parity).
static std::string envelope(bool success, const std::string& message,
                            const std::string& data_json) {
  std::string out = "{\"success\":";
  out += success ? "true" : "false";
  out += ",\"message\":";
  json_escape_to(out, message);
  out += ",\"data\":";
  out += data_json.empty() ? "null" : data_json;
  out += "}";
  return out;
}

// ---- journal records (requests.go:27-49 shape, journal.py field parity) ----

struct JEntry {
  std::string rid, agent_id, method, path;
  std::vector<std::pair<std::string, std::string>> headers;
  std::string body;
  double created_at = 0;
};

static std::string record_json(const JEntry& e, const std::string& status,
                               int retry_count, const std::string& error,
                               const std::string& response_json) {
  std::string out = "{\"id\":";
  json_escape_to(out, e.rid);
  out += ",\"agent_id\":";
  json_escape_to(out, e.agent_id);
  out += ",\"method\":";
  json_escape_to(out, e.method);
  out += ",\"path\":";
  json_escape_to(out, e.path);
  out += ",\"headers\":{";
  bool first = true;
  for (const auto& kv : e.headers) {
    if (!first) out += ",";
    first = false;
    json_escape_to(out, kv.first);
    out += ":";
    json_escape_to(out, kv.second);
  }
  out += "},\"body_b64\":\"" + (e.body.empty() ? "" : b64_encode(e.body));
  out += "\",\"status\":";
  json_escape_to(out, status);
  out += ",\"retry_count\":" + std::to_string(retry_count);
  out += ",\"max_retries\":3,\"response\":";
  out += response_json.empty() ? "null" : response_json;
  out += ",\"error\":";
  json_escape_to(out, error);
  char ts[64];
  std::snprintf(ts, sizeof(ts), ",\"created_at\":%.6f,\"updated_at\":%.6f}",
                e.created_at, now_s());
  out += ts;
  return out;
}

// ---- store helpers (direct, no wire round-trip needed in-process) ----------

static void store_set_at(Store* s, const std::string& key, const std::string& val,
                         double expire_at) {
  Request r;
  r.op = OP_SETEXAT;
  r.args = {key, val, expire_at < 0 ? "" : std::to_string(expire_at)};
  s->execute(r);
}

static void store_rpush(Store* s, const std::string& key, const std::string& val) {
  Request r;
  r.op = OP_RPUSH;
  r.args = {key, val};
  s->execute(r);
}

static void store_lrem1(Store* s, const std::string& key, const std::string& val) {
  Request r;
  r.op = OP_LREM;
  r.args = {key, "1", val};
  s->execute(r);
}

static std::string store_get(Store* s, const std::string& key, bool* found) {
  Request r;
  r.op = OP_GET;
  r.args = {key};
  std::string resp = s->execute(r);
  if (resp.empty() || resp[0] != RESP_OK) {
    *found = false;
    return "";
  }
  *found = true;
  // [status u8][count u32][len u32][bytes]
  if (resp.size() < 9) {
    *found = false;
    return "";
  }
  uint32_t len = get_u32(reinterpret_cast<const uint8_t*>(resp.data() + 5));
  return resp.substr(9, len);
}

static constexpr double REQUEST_TTL_S = 24 * 3600;  // requests.go:106
// How long a forward waits for the upstream's answer. The management backend
// answers at once or is broken (UPSTREAM_WAIT_S), but for the one call that
// waits for an engine behind it: POST /agents/{id}/profile answers after the
// capture AND the collection of its trace, which grows with the device events
// in the window (5 s of a 17 ms decode step over 16 layers took over 25 s to
// collect, and a fixed 30 s answered 502 for a capture that then completed).
// The backend bounds that call itself (duration_s <= 60), so its answer is
// waited for like a dispatch without a deadline. An agent dispatch waits as
// long as its caller does: the request's X-Agentainer-Deadline-Ms (plus a
// grace in which the engine's own "expired" answer arrives first), and without
// one as long as a whole buffered generation may take (2048 tokens at 50 ms a
// step are 100 s; a fixed 30 s failed healthy generations as "unreachable").
// An engine that dies closes its socket and is seen at once; this bound only
// catches one that hangs with its socket open.
static constexpr double UPSTREAM_WAIT_S = 30;
static constexpr double DISPATCH_WAIT_S = 600;
static constexpr double DEADLINE_GRACE_S = 1;

// ---- DataPlane -------------------------------------------------------------

DataPlane::DataPlane(Store* store, const std::string& listen_host, int listen_port,
                     const std::string& backend_host, int backend_port,
                     const std::string& uds_path)
    : store_(store),
      listen_host_(listen_host),
      listen_port_(listen_port),
      backend_host_(backend_host),
      backend_port_(backend_port),
      uds_path_(uds_path) {}

DataPlane::~DataPlane() { stop(); }

static int make_tcp_listener(const std::string& host, int port, int* actual_port) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  int one = 1;
  ::setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  // honor the configured bind host (the aiohttp fallback does) — a
  // loopback-only config must not expose the unauthenticated /agent/* path
  if (host.empty() || host == "0.0.0.0") {
    addr.sin_addr.s_addr = htonl(INADDR_ANY);
  } else if (::inet_pton(AF_INET, host.c_str(), &addr.sin_addr) != 1) {
    ::close(fd);
    return -1;
  }
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (::bind(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) < 0 ||
      ::listen(fd, 512) < 0) {
    ::close(fd);
    return -1;
  }
  socklen_t alen = sizeof(addr);
  ::getsockname(fd, reinterpret_cast<sockaddr*>(&addr), &alen);
  *actual_port = ntohs(addr.sin_port);
  return fd;
}

bool DataPlane::start() {
  listen_fd_ = make_tcp_listener(listen_host_, listen_port_, &port_);
  if (listen_fd_ < 0) return false;
  if (!uds_path_.empty()) {
    ::unlink(uds_path_.c_str());
    uds_fd_ = ::socket(AF_UNIX, SOCK_STREAM, 0);
    sockaddr_un ua{};
    ua.sun_family = AF_UNIX;
    std::strncpy(ua.sun_path, uds_path_.c_str(), sizeof(ua.sun_path) - 1);
    if (::bind(uds_fd_, reinterpret_cast<sockaddr*>(&ua), sizeof(ua)) < 0 ||
        ::listen(uds_fd_, 128) < 0) {
      ::close(uds_fd_);
      ::close(listen_fd_);
      return false;
    }
  }
  accept_thread_ = std::thread([this] { accept_loop(listen_fd_, false); });
  if (uds_fd_ >= 0)
    uds_thread_ = std::thread([this] { accept_loop(uds_fd_, true); });
  settle_thread_ = std::thread([this] { settle_loop(); });
  return true;
}

void DataPlane::settle_enqueue(std::function<void()> fn) {
  bool inline_run = false;
  {
    std::lock_guard<std::mutex> lk(settle_mu_);
    if (settle_stop_ || settle_q_.size() > 100000) {
      // stopping or badly backed up: apply inline (backpressure) rather
      // than drop — journal consistency over latency. The store I/O runs
      // OUTSIDE the lock so overload doesn't serialize every conn thread.
      inline_run = true;
    } else {
      settle_q_.push_back(std::move(fn));
    }
  }
  if (inline_run) {
    fn();
    return;
  }
  settle_cv_.notify_one();
}

void DataPlane::settle_loop() {
  std::unique_lock<std::mutex> lk(settle_mu_);
  for (;;) {
    settle_cv_.wait(lk, [this] { return settle_stop_ || !settle_q_.empty(); });
    while (!settle_q_.empty()) {
      auto fn = std::move(settle_q_.front());
      settle_q_.pop_front();
      lk.unlock();
      fn();
      lk.lock();
    }
    if (settle_stop_) return;
  }
}

void DataPlane::stop() {
  if (stopping_.exchange(true)) return;
  if (listen_fd_ >= 0) ::shutdown(listen_fd_, SHUT_RDWR), ::close(listen_fd_);
  if (uds_fd_ >= 0) ::shutdown(uds_fd_, SHUT_RDWR), ::close(uds_fd_);
  {
    std::lock_guard<std::mutex> lk(conn_mu_);
    for (int fd : conns_) ::shutdown(fd, SHUT_RDWR);
  }
  if (accept_thread_.joinable()) accept_thread_.join();
  if (uds_thread_.joinable()) uds_thread_.join();
  // wait for detached connection threads to leave store code — the owner
  // frees the store right after stop() returns. All their fds (client AND
  // upstream) were just shutdown(), so blocked recvs return immediately.
  for (int i = 0; i < 500 && active_conns_.load() > 0; i++)
    std::this_thread::sleep_for(std::chrono::milliseconds(10));
  // conn threads can no longer enqueue (settle_enqueue under settle_mu_ runs
  // inline once settle_stop_ is set); drain what's queued, then join
  {
    std::lock_guard<std::mutex> lk(settle_mu_);
    settle_stop_ = true;
  }
  settle_cv_.notify_one();
  if (settle_thread_.joinable()) settle_thread_.join();
  if (!uds_path_.empty()) ::unlink(uds_path_.c_str());
}

void DataPlane::track(int fd, bool add) {
  std::lock_guard<std::mutex> lk(conn_mu_);
  if (add)
    conns_.insert(fd);
  else
    conns_.erase(fd);
}

void DataPlane::accept_loop(int fd, bool uds) {
  for (;;) {
    int conn = ::accept(fd, nullptr, nullptr);
    if (conn < 0) {
      if (stopping_) return;
      // EMFILE/EINTR etc.: back off instead of spinning the core
      std::this_thread::sleep_for(std::chrono::milliseconds(10));
      continue;
    }
    if (!uds) {
      int one = 1;
      ::setsockopt(conn, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    }
    track(conn, true);
    std::thread t(uds ? &DataPlane::handle_uds_conn : &DataPlane::handle_conn, this,
                  conn);
    t.detach();
  }
}

void DataPlane::route_set(const std::string& agent_id, const std::string& host,
                          int port, const std::string& status, bool persist) {
  std::lock_guard<std::mutex> lk(route_mu_);
  routes_[agent_id] = Route{host, port, status, persist};
}

void DataPlane::route_del(const std::string& agent_id) {
  std::lock_guard<std::mutex> lk(route_mu_);
  routes_.erase(agent_id);
}

void DataPlane::counters_drain(const std::string& agent_id, uint64_t* requests,
                               double* latency_sum, double* latency_max) {
  std::lock_guard<std::mutex> lk(counter_mu_);
  auto it = counters_.find(agent_id);
  if (it == counters_.end()) {
    *requests = 0;
    *latency_sum = 0;
    *latency_max = 0;
    return;
  }
  *requests = it->second.requests;
  *latency_sum = it->second.lat_sum;
  *latency_max = it->second.lat_max;
  counters_.erase(it);
}

// Per-connection context: owns upstream keepalive sockets.
struct ConnCtx {
  DataPlane* dp;
  int client_fd;
  std::unordered_map<std::string, int> upstream;  // "host:port" -> fd
  std::unordered_map<std::string, std::string> upstream_buf;

  ~ConnCtx() {
    for (auto& kv : upstream) {
      dp->track(kv.second, false);
      ::close(kv.second);
    }
  }

  void drop(const std::string& key, int fd) {
    dp->track(fd, false);
    ::close(fd);
    upstream.erase(key);
    upstream_buf.erase(key);
  }

  int connect_to(const std::string& host, int port, bool* refused) {
    *refused = false;
    // upstream fds are tracked in dp->conns_ so stop() can shutdown() them —
    // otherwise a conn thread blocked in a 30s upstream recv outlives stop()
    // and touches the store after the owner frees it
    if (dp->stopping_.load()) return -1;
    int fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return -1;
    int one = 1;
    ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    timeval tv{static_cast<time_t>(UPSTREAM_WAIT_S), 0};
    ::setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
    ::setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons(static_cast<uint16_t>(port));
    if (::inet_pton(AF_INET, host.c_str(), &addr.sin_addr) != 1) {
      // only numeric hosts expected (localhost engines); try 127.0.0.1
      ::inet_pton(AF_INET, "127.0.0.1", &addr.sin_addr);
    }
    if (::connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) < 0) {
      *refused = (errno == ECONNREFUSED || errno == ENOENT || errno == EHOSTUNREACH);
      ::close(fd);
      return -1;
    }
    dp->track(fd, true);
    // close the race where stop() snapshots conns_ between our stopping_
    // check and track(): self-shutdown so the pending recv fails fast
    if (dp->stopping_.load()) ::shutdown(fd, SHUT_RDWR);
    return fd;
  }

  // Send req to host:port reusing a cached connection; one silent retry on a
  // stale keepalive socket. Outcomes: 0 ok, 1 connection-refused/engine-gone,
  // 2 other failure (timeout / protocol error). `head` marks a HEAD request,
  // whose response advertises Content-Length without sending a body.
  // `wait_s` is how long the answer is waited for; a wait that runs out is
  // outcome 2 and is not retried (the upstream has the request).
  int roundtrip(const std::string& host, int port, const std::string& raw_req,
                HttpMsg* resp, bool head = false, double wait_s = UPSTREAM_WAIT_S) {
    std::string key = host + ":" + std::to_string(port);
    for (int attempt = 0; attempt < 2; attempt++) {
      bool fresh = false;
      auto it = upstream.find(key);
      int fd;
      if (it == upstream.end()) {
        bool refused = false;
        fd = connect_to(host, port, &refused);
        if (fd < 0) return refused ? 1 : 2;
        upstream[key] = fd;
        upstream_buf[key].clear();
        fresh = true;
      } else {
        fd = it->second;
      }
      // per roundtrip: a kept-alive socket serves requests of any deadline
      timeval tv{static_cast<time_t>(wait_s),
                 static_cast<suseconds_t>((wait_s - std::floor(wait_s)) * 1e6)};
      ::setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
      if (!send_all(fd, raw_req)) {
        drop(key, fd);
        if (fresh) return 1;  // engine accepted then died: treat as gone
        continue;             // stale keepalive: retry once with fresh conn
      }
      SockBuf sb(fd);
      sb.buf = std::move(upstream_buf[key]);
      bool eof_clean = false;
      if (!read_http(sb, true, resp, &eof_clean, head)) {
        drop(key, fd);
        if (dp->stopping_.load()) return 2;
        if (!fresh && eof_clean) continue;  // stale keepalive
        return fresh && eof_clean ? 1 : 2;
      }
      upstream_buf[key] = std::move(sb.buf);
      if (!resp->keepalive) drop(key, fd);
      return 0;
    }
    return 2;
  }
};

// Build the raw upstream request for an agent dispatch or backend forward.
// `accepted_ns` (agent dispatch only; 0: none) is when this front door had
// read the request, before its journal write: the engine samples its
// distance to it (core/protocol.py ACCEPTED_NS_HEADER).
static std::string build_upstream_request(
    const std::string& method, const std::string& target,
    const std::vector<std::pair<std::string, std::string>>& headers,
    const std::string& body, const std::string& host_hdr,
    const std::string& request_id, bool strip_auth, long long accepted_ns = 0) {
  std::string out = method + " " + target + " HTTP/1.1\r\n";
  out += "Host: " + host_hdr + "\r\n";
  for (const auto& kv : headers) {
    std::string l = lower(kv.first);
    if (is_hop_by_hop(l)) continue;
    if (strip_auth && l == "authorization") continue;
    if (l == "x-agentainer-request-id" || l == "x-agentainer-replay") continue;
    out += kv.first + ": " + kv.second + "\r\n";
  }
  if (!request_id.empty()) out += "X-Agentainer-Request-ID: " + request_id + "\r\n";
  if (accepted_ns > 0)
    out += "X-Agentainer-Accepted-Ns: " + std::to_string(accepted_ns) + "\r\n";
  out += "Content-Length: " + std::to_string(body.size()) + "\r\n";
  out += "Connection: keep-alive\r\n\r\n";
  out += body;
  return out;
}

void DataPlane::handle_conn(int fd) {
  active_conns_++;
  ConnCtx ctx{this, fd};
  SockBuf sb(fd);
  timeval tv{75, 0};
  ::setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));

  for (;;) {
    HttpMsg req;
    if (!read_http(sb, false, &req)) break;

    bool keep = req.keepalive;
    std::string resp_raw;

    if (req.target.rfind("/agent/", 0) == 0) {
      // ---- native proxy path ------------------------------------------
      size_t id_start = 7;
      size_t id_end = req.target.find_first_of("/?", id_start);
      std::string agent_id = req.target.substr(
          id_start, id_end == std::string::npos ? std::string::npos : id_end - id_start);
      std::string path = "/";
      if (id_end != std::string::npos) {
        if (req.target[id_end] == '/') {
          path = req.target.substr(id_end);
        } else {
          path = "/" + req.target.substr(id_end);  // bare ?query
        }
      }

      Route route;
      bool have_route = false;
      {
        std::lock_guard<std::mutex> lk(route_mu_);
        auto it = routes_.find(agent_id);
        if (it != routes_.end()) {
          route = it->second;
          have_route = true;
        }
      }
      if (!have_route) {
        resp_raw = build_response(
            404, {}, envelope(false, "agent not found: " + agent_id, ""), keep);
        if (!send_all(fd, resp_raw) || !keep) break;
        continue;
      }

      if (route.port == 0 && route.status == "running") {
        // python-owned route (replica fleet): a RUNNING agent with no
        // single endpoint means the aiohttp proxy owns its dispatch —
        // replica choice, session affinity, bounded cross-replica retry,
        // AND the journaling. Fall through to the management forward
        // below with the request untouched instead of dispatching
        // natively to one endpoint (which is exactly the primary-only
        // blind spot the routing tier exists to fix).
      } else {

      long long accepted_ns = now_ns();  // read, not yet journaled

      // journal entry (before dispatch — the signature guarantee)
      JEntry e;
      e.agent_id = agent_id;
      e.method = req.method;
      e.path = path;
      e.body = req.body;
      e.created_at = now_s();
      for (const auto& kv : req.headers) {
        std::string l = lower(kv.first);
        // the accept stamp is this front door's alone to set, and is not
        // journaled: a replayed dispatch carries none
        if (is_hop_by_hop(l) || l == "x-agentainer-replay" ||
            l == "x-agentainer-request-id" || l == "x-agentainer-accepted-ns")
          continue;
        e.headers.push_back(kv);
      }
      std::string rec_key;
      double rec_deadline = e.created_at + REQUEST_TTL_S;
      if (route.persist) {
        e.rid = uuid4();
        rec_key = "agent:" + agent_id + ":requests:" + e.rid;
        store_set_at(store_, rec_key, record_json(e, "pending", 0, "", ""),
                     rec_deadline);
        store_rpush(store_, "agent:" + agent_id + ":requests:pending", e.rid);
      }

      if (route.status != "running") {
        if (route.persist) {
          resp_raw = build_response(
              202, {},
              envelope(true,
                       "Agent is not running. Request queued and will be "
                       "replayed when the agent is back.",
                       "{\"request_id\":" + json_escape(e.rid) +
                           ",\"status\":\"pending\"}"),
              keep);
        } else {
          resp_raw =
              build_response(503, {}, envelope(false, "agent is not running", ""), keep);
        }
        if (!send_all(fd, resp_raw) || !keep) break;
        continue;
      }

      // pending→processing BEFORE dispatch: the replay worker's 5 s tick
      // re-dispatches PENDING entries of a running agent, so an in-flight
      // generation longer than one tick would execute twice without this
      // marker (journal.py stale-reclaim returns it to pending if we die)
      if (route.persist)
        store_set_at(store_, rec_key, record_json(e, "processing", 0, "", ""),
                     rec_deadline);

      std::string upstream_req = build_upstream_request(
          req.method, path, e.headers, req.body,
          route.host + ":" + std::to_string(route.port), e.rid, /*strip_auth=*/true,
          accepted_ns);
      HttpMsg up;
      double t0 = mono_s();
      double deadline_ms = std::atof(req.header("x-agentainer-deadline-ms").c_str());
      double wait_s =
          deadline_ms > 0 ? deadline_ms / 1000.0 + DEADLINE_GRACE_S : DISPATCH_WAIT_S;
      int rc = ctx.roundtrip(route.host, route.port, upstream_req, &up,
                             req.method == "HEAD", wait_s);
      double dt = mono_s() - t0;

      bool loading = rc == 0 && up.status == 503 &&
                     lower(up.header("x-agentainer-loading")) == "true";
      if (rc == 1 || loading) {
        // engine gone (or still loading): entry returns to pending for the
        // replay worker; no retry charged (server.go:597-606 heuristic)
        if (route.persist)
          store_set_at(store_, rec_key, record_json(e, "pending", 0, "", ""),
                       rec_deadline);
        resp_raw = build_response(
            502, {},
            envelope(false, "agent unreachable; request left pending for replay", ""),
            keep);
      } else if (rc == 2) {
        // timeout / protocol error: first retry charged (journal.mark_failed
        // semantics — dp-originated entries always carry retry_count 0 here)
        if (route.persist)
          store_set_at(store_, rec_key,
                       record_json(e, "pending", 1, "dispatch failed", ""),
                       rec_deadline);
        resp_raw = build_response(
            504, {}, envelope(false, "agent request failed; retry recorded", ""), keep);
      } else {
        if (route.persist) {
          // settle off-path: archive the response + move pending→completed
          // on the background thread. The client's response doesn't wait
          // for archive I/O; the at-most-ms window where a replay tick
          // could see a completed entry still pending is covered by engine
          // idempotency (request-id memoization).
          Store* store = store_;
          settle_enqueue([store, e, agent_id, rec_key, rec_deadline, up]() {
            std::string resp_json = "{\"status_code\":" +
                                    std::to_string(up.status) + ",\"headers\":{";
            bool first = true;
            for (const auto& kv : up.headers) {
              if (!first) resp_json += ",";
              first = false;
              json_escape_to(resp_json, kv.first);
              resp_json += ":";
              json_escape_to(resp_json, kv.second);
            }
            resp_json += "},\"body_b64\":\"" +
                         (up.body.empty() ? "" : b64_encode(up.body)) + "\"}";
            store_set_at(store, rec_key,
                         record_json(e, "completed", 0, "", resp_json),
                         rec_deadline);
            store_lrem1(store, "agent:" + agent_id + ":requests:pending", e.rid);
            store_rpush(store, "agent:" + agent_id + ":requests:completed", e.rid);
          });
        }
        {
          std::lock_guard<std::mutex> lk(counter_mu_);
          Counter& c = counters_[agent_id];
          c.requests++;
          c.lat_sum += dt;
          c.lat_max = std::max(c.lat_max, dt);
        }
        if (route.persist)
          // span continuity: the journal id rides back to the caller so a
          // response correlates with /agents/{id}/requests + engine logs
          up.headers.emplace_back("X-Agentainer-Request-ID", e.rid);
        resp_raw = build_response(
            up.status, up.headers, up.body, keep,
            req.method == "HEAD" ? up.header("content-length") : "");
      }
      if (!send_all(fd, resp_raw) || !keep) break;
      continue;
      }  // end native-dispatch branch (python-owned routes fall through)
    }

    // ---- management path: forward verbatim to the Python server ----------
    std::string fwd = build_upstream_request(
        req.method, req.target, req.headers, req.body,
        backend_host_ + ":" + std::to_string(backend_port_), "", /*strip_auth=*/false);

    // log-follow responses never end: relay bytes as they arrive instead of
    // buffering the (unbounded) body through roundtrip(). Dedicated upstream
    // connection; both sockets close when either side goes away.
    // Match the Python handler's semantics: follow present and not 0/false.
    bool follow_stream = false;
    if (req.target.find("/logs") != std::string::npos) {
      size_t fpos = req.target.find("follow=");
      if (fpos != std::string::npos) {
        std::string val = req.target.substr(fpos + 7);
        size_t amp = val.find('&');
        if (amp != std::string::npos) val = val.substr(0, amp);
        follow_stream = !val.empty() && val != "0" && lower(val) != "false";
      }
    }
    if (follow_stream) {
      bool refused = false;
      int ufd = ctx.connect_to(backend_host_, backend_port_, &refused);
      if (ufd < 0 || !send_all(ufd, fwd)) {
        if (ufd >= 0) {
          track(ufd, false);
          ::close(ufd);
        }
        resp_raw = build_response(
            502, {}, envelope(false, "management backend unavailable", ""), false);
        send_all(fd, resp_raw);
        break;
      }
      // follow streams idle between log lines: poll BOTH sockets so an
      // upstream line relays promptly AND a client disconnect during an
      // idle stream tears the relay down (no leaked thread/fds)
      char buf[1 << 14];
      for (;;) {
        pollfd fds[2];
        fds[0] = {ufd, POLLIN, 0};
        fds[1] = {fd, POLLIN | POLLRDHUP, 0};
        int pr = ::poll(fds, 2, 1000);
        if (pr < 0) break;
        if (pr == 0) {
          if (stopping_.load()) break;
          continue;
        }
        if (fds[1].revents) {
          // bytes from the client mid-stream or HUP: either way, done —
          // a follow response accepts no further requests on this conn
          break;
        }
        if (fds[0].revents) {
          ssize_t n = ::recv(ufd, buf, sizeof(buf), 0);
          if (n <= 0) break;
          if (!send_all(fd, buf, static_cast<size_t>(n))) break;
        }
      }
      track(ufd, false);
      ::close(ufd);
      break;  // stream consumed the connection
    }

    // the one management call that waits for an engine (see DISPATCH_WAIT_S)
    std::string path = req.target.substr(0, req.target.find('?'));
    bool profile = req.method == "POST" && path.rfind("/agents/", 0) == 0 &&
                   path.size() > 8 &&
                   path.compare(path.size() - 8, 8, "/profile") == 0;
    HttpMsg up;
    int rc = ctx.roundtrip(backend_host_, backend_port_, fwd, &up,
                           req.method == "HEAD",
                           profile ? DISPATCH_WAIT_S : UPSTREAM_WAIT_S);
    if (rc != 0) {
      resp_raw = build_response(
          502, {}, envelope(false, "management backend unavailable", ""), keep);
    } else {
      resp_raw = build_response(
          up.status, up.headers, up.body, keep,
          req.method == "HEAD" ? up.header("content-length") : "");
    }
    if (!send_all(fd, resp_raw)) break;
    if (!keep) break;
  }
  track(fd, false);
  ::close(fd);
  active_conns_--;
}

// ---- UDS store protocol: [u32 len][encoded request] per frame --------------

void DataPlane::handle_uds_conn(int fd) {
  active_conns_++;
  SockBuf sb(fd);
  std::string ns;  // set after AUTH
  for (;;) {
    std::string len_raw;
    if (!sb.read_exact(4, &len_raw)) break;
    uint32_t len = get_u32(reinterpret_cast<const uint8_t*>(len_raw.data()));
    if (len > (64u << 20)) break;
    std::string frame;
    if (!sb.read_exact(len, &frame)) break;
    Request req;
    std::string resp;
    if (!parse_request(reinterpret_cast<const uint8_t*>(frame.data()), frame.size(),
                       &req)) {
      resp = resp_err("malformed request");
    } else if (req.op == OP_AUTH) {
      if (req.args.size() != 2) {
        resp = resp_err("AUTH needs agent_id token");
      } else {
        bool found = false;
        std::string expected = store_get(store_, "internal:token:" + req.args[0], &found);
        if (!found || expected.empty() || expected != req.args[1]) {
          resp = resp_err("invalid engine credentials");
        } else {
          ns = "agent:" + req.args[0] + ":";
          resp = resp_ok();
        }
      }
    } else if (ns.empty()) {
      resp = resp_err("AUTH required");
    } else {
      resp = store_->execute(req, ns);
    }
    std::string framed;
    put_u32(framed, static_cast<uint32_t>(resp.size()));
    framed += resp;
    if (!send_all(fd, framed)) break;
  }
  track(fd, false);
  ::close(fd);
  active_conns_--;
}

}  // namespace atpu
