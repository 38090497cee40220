// pdtpu_native: C++ runtime components for paddle_tpu.
//
// Reference parity (SURVEY §2.4/§2.6): the reference implements its
// rendezvous store (paddle/fluid/distributed/store/tcp_store.cc), reader
// blocking queue (paddle/fluid/operators/reader/ + blocking_queue.h), and
// batch collation in C++. These are their TPU-host equivalents:
//
//   1. TCPStore server — same length-prefixed wire protocol as the Python
//      client in paddle_tpu/launch/store.py (u32 nfields, then per field
//      u32 len + bytes). Runs the rendezvous/elastic-heartbeat store
//      without ever touching the training process's GIL.
//   2. BlockingQueue — bounded MPMC queue of byte blocks (the reference's
//      reader blocking queue role) for the DataLoader prefetch pipeline.
//   3. collate_stack — batched memcpy (np.stack equivalent) callable with
//      the GIL released, so a DataLoader thread pool actually scales.
//
// Built with: g++ -O2 -fPIC -shared -pthread -o libpdtpu_native.so
// No Python.h dependency — pure C ABI consumed via ctypes.

#include <arpa/inet.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <list>
#include <map>
#include <mutex>
#include <stdexcept>
#include <string>
#include <system_error>
#include <thread>
#include <vector>

namespace {

// ---------------------------------------------------------------------------
// wire helpers (protocol shared with paddle_tpu/launch/store.py)
// ---------------------------------------------------------------------------

bool read_exact(int fd, void* buf, size_t n) {
  char* p = static_cast<char*>(buf);
  while (n > 0) {
    ssize_t r = ::recv(fd, p, n, 0);
    if (r < 0 && errno == EINTR) continue;
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

bool write_all(int fd, const void* buf, size_t n) {
  const char* p = static_cast<const char*>(buf);
  while (n > 0) {
    ssize_t r = ::send(fd, p, n, MSG_NOSIGNAL);
    if (r < 0 && errno == EINTR) continue;
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

// u32 little-endian on the wire (struct '<I' on the Python side) —
// explicit conversion keeps the protocol byte-order portable
uint32_t le32_decode(const void* p) {
  const unsigned char* b = static_cast<const unsigned char*>(p);
  return static_cast<uint32_t>(b[0]) | (static_cast<uint32_t>(b[1]) << 8) |
         (static_cast<uint32_t>(b[2]) << 16) |
         (static_cast<uint32_t>(b[3]) << 24);
}

void le32_encode(uint32_t v, std::string* out) {
  char b[4] = {static_cast<char>(v & 0xff), static_cast<char>((v >> 8) & 0xff),
               static_cast<char>((v >> 16) & 0xff),
               static_cast<char>((v >> 24) & 0xff)};
  out->append(b, 4);
}

bool read_msg(int fd, std::vector<std::string>* fields) {
  char nf_raw[4];
  if (!read_exact(fd, nf_raw, 4)) return false;
  uint32_t nf = le32_decode(nf_raw);
  if (nf > 1024) return false;  // sanity bound
  fields->clear();
  for (uint32_t i = 0; i < nf; ++i) {
    char len_raw[4];
    if (!read_exact(fd, len_raw, 4)) return false;
    uint32_t len = le32_decode(len_raw);
    if (len > (64u << 20)) return false;  // 64 MiB per field bound
    std::string f(len, '\0');
    if (len && !read_exact(fd, &f[0], len)) return false;
    fields->push_back(std::move(f));
  }
  return true;
}

bool write_msg(int fd, const std::vector<std::string>& fields) {
  std::string out;
  le32_encode(static_cast<uint32_t>(fields.size()), &out);
  for (const auto& f : fields) {
    le32_encode(static_cast<uint32_t>(f.size()), &out);
    out.append(f);
  }
  return write_all(fd, out.data(), out.size());
}

// ---------------------------------------------------------------------------
// TCPStore server
// ---------------------------------------------------------------------------

class StoreServer {
 public:
  StoreServer() = default;

  int Start(const char* host, int port) {
    listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    if (listen_fd_ < 0) return -1;
    int one = 1;
    ::setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons(static_cast<uint16_t>(port));
    addr.sin_addr.s_addr = htonl(INADDR_ANY);
    if (host && *host) {
      // hostname or dotted quad — resolve like Python's socket.bind does
      addrinfo hints{};
      hints.ai_family = AF_INET;
      hints.ai_socktype = SOCK_STREAM;
      addrinfo* res = nullptr;
      if (::getaddrinfo(host, nullptr, &hints, &res) != 0 || !res) {
        ::close(listen_fd_);
        listen_fd_ = -1;
        return -1;
      }
      addr.sin_addr = reinterpret_cast<sockaddr_in*>(res->ai_addr)->sin_addr;
      ::freeaddrinfo(res);
    }
    if (::bind(listen_fd_, reinterpret_cast<sockaddr*>(&addr),
               sizeof(addr)) < 0 ||
        ::listen(listen_fd_, 128) < 0) {
      ::close(listen_fd_);
      listen_fd_ = -1;
      return -1;
    }
    socklen_t alen = sizeof(addr);
    ::getsockname(listen_fd_, reinterpret_cast<sockaddr*>(&addr), &alen);
    bound_port_ = ntohs(addr.sin_port);
    accept_thread_ = std::thread([this] { AcceptLoop(); });
    return bound_port_;
  }

  void Stop() {
    {
      std::lock_guard<std::mutex> lk(mu_);
      stopping_ = true;
      cv_.notify_all();
    }
    // shutdown unblocks accept(); the fd is CLOSED only after the accept
    // thread joins, so a racing accept() can never hit a reused fd number
    if (listen_fd_ >= 0) ::shutdown(listen_fd_, SHUT_RDWR);
    if (accept_thread_.joinable()) accept_thread_.join();
    if (listen_fd_ >= 0) {
      ::close(listen_fd_);
      listen_fd_ = -1;
    }
    // No accept thread any more, so conns_ is final.  Unblock every worker
    // parked in recv()/send() on a live connection, then join OUTSIDE the
    // mutex: a worker's last act takes conns_mu_.  A worker closes its fd
    // under that mutex, so the fds shut down here are exactly the open ones.
    std::list<Conn> conns;
    {
      std::lock_guard<std::mutex> lk(conns_mu_);
      for (Conn& c : conns_)
        if (c.fd >= 0) ::shutdown(c.fd, SHUT_RDWR);
      conns.swap(conns_);
    }
    for (Conn& c : conns) c.th.join();
  }

  ~StoreServer() { Stop(); }

 private:
  // One accepted connection and the thread that serves it.  `fd` and `done`
  // belong to conns_mu_; std::list keeps the address stable for the worker.
  struct Conn {
    int fd = -1;
    bool done = false;
    std::thread th;
  };

  void AcceptLoop() {
    while (true) {
      int fd = ::accept(listen_fd_, nullptr, nullptr);
      if (fd < 0) {
        int err = errno;
        if (stopping_ || err == EBADF || err == EINVAL || err == ENOTSOCK)
          return;  // Stop() shut the listener down, or it is gone
        // EINTR, ECONNABORTED (peer reset before accept), EPROTO...: the
        // listener still completes handshakes, so it must still be served.
        // Out of fds/memory the queued connection stays queued: don't spin.
        if (err == EMFILE || err == ENFILE || err == ENOBUFS || err == ENOMEM)
          std::this_thread::sleep_for(std::chrono::milliseconds(10));
        continue;
      }
      int one = 1;
      ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      // reap finished workers so a long-lived server doesn't accumulate
      // one joinable thread (and its retained stack) per past connection;
      // `done` is the worker's own flag (a thread id is reused as soon as
      // its thread is joined and says nothing about a later worker)
      std::list<Conn> finished;
      {
        std::lock_guard<std::mutex> lk(conns_mu_);
        for (auto it = conns_.begin(); it != conns_.end();) {
          auto cur = it++;
          if (cur->done) finished.splice(finished.end(), conns_, cur);
        }
        conns_.emplace_back();
        Conn* c = &conns_.back();
        c->fd = fd;
        try {
          c->th = std::thread([this, c] { Serve(c); });
        } catch (const std::system_error&) {  // no thread to be had
          ::close(fd);
          conns_.pop_back();
        }
      }
      for (Conn& c : finished) c.th.join();  // past `done`: returns at once
    }
  }

  void Serve(Conn* c) {
    const int fd = c->fd;
    std::vector<std::string> req;
    while (read_msg(fd, &req)) {
      if (req.empty()) break;
      std::vector<std::string> resp;
      try {
        resp = Dispatch(req);
      } catch (const std::exception&) {
        // malformed field (e.g. add on a non-numeric value): fail THIS
        // request, keep the server alive — matches the Python server where
        // socketserver contains per-connection exceptions
        resp = {"error"};
      }
      if (!write_msg(fd, resp)) break;
    }
    // close under the mutex: once the number is free accept() may hand it
    // to a new connection, and Stop() must never shut that one down for this
    std::lock_guard<std::mutex> lk(conns_mu_);
    ::close(fd);
    c->fd = -1;
    c->done = true;
  }

  std::vector<std::string> Dispatch(const std::vector<std::string>& req) {
    const std::string& op = req[0];
    static const std::map<std::string, size_t> kArity = {
        {"set", 3}, {"get", 2}, {"add", 3}, {"delete", 2},
        {"cas", 4}, {"list", 2}, {"wait", 3}};
    auto ar = kArity.find(op);
    if (ar != kArity.end() && req.size() < ar->second)
      throw std::out_of_range("short store message");
    std::unique_lock<std::mutex> lk(mu_);
    if (op == "set") {
      kv_[req[1]] = req[2];
      cv_.notify_all();
      return {"ok"};
    }
    if (op == "get") {
      auto it = kv_.find(req[1]);
      if (it == kv_.end()) return {"miss"};
      return {"ok", it->second};
    }
    if (op == "add") {
      long long cur = 0;
      auto it = kv_.find(req[1]);
      if (it != kv_.end()) cur = std::stoll(it->second);
      cur += std::stoll(req[2]);
      kv_[req[1]] = std::to_string(cur);
      cv_.notify_all();
      return {"ok", std::to_string(cur)};
    }
    if (op == "delete") {
      bool existed = kv_.erase(req[1]) > 0;
      cv_.notify_all();
      return {existed ? "ok" : "miss"};
    }
    if (op == "cas") {
      auto it = kv_.find(req[1]);
      bool match = (it == kv_.end() && req[2].empty()) ||
                   (it != kv_.end() && it->second == req[2]);
      if (match) {
        kv_[req[1]] = req[3];
        cv_.notify_all();
        return {"ok", req[3]};
      }
      return {"miss", it == kv_.end() ? std::string() : it->second};
    }
    if (op == "list") {
      std::vector<std::string> out{"ok"};
      for (const auto& p : kv_)
        if (p.first.rfind(req[1], 0) == 0) out.push_back(p.first);
      return out;
    }
    if (op == "wait") {
      double timeout_s = std::stod(req[2]);
      auto deadline = std::chrono::steady_clock::now() +
                      std::chrono::duration<double>(timeout_s);
      while (kv_.find(req[1]) == kv_.end() && !stopping_) {
        if (cv_.wait_until(lk, deadline) == std::cv_status::timeout)
          return {"timeout"};
      }
      auto it = kv_.find(req[1]);
      if (it == kv_.end()) return {"timeout"};
      return {"ok", it->second};
    }
    return {"badop"};
  }

  int listen_fd_ = -1;
  int bound_port_ = -1;
  std::atomic<bool> stopping_{false};
  std::thread accept_thread_;
  std::list<Conn> conns_;
  std::mutex conns_mu_;
  std::map<std::string, std::string> kv_;
  std::mutex mu_;
  std::condition_variable cv_;
};

// ---------------------------------------------------------------------------
// BlockingQueue of byte blocks
// ---------------------------------------------------------------------------

struct Block {
  char* data;
  size_t size;
};

class BlockingQueue {
 public:
  explicit BlockingQueue(size_t capacity) : capacity_(capacity) {}

  ~BlockingQueue() {
    std::lock_guard<std::mutex> lk(mu_);
    for (auto& b : q_) ::free(b.data);
    q_.clear();
  }

  // returns 0 on success, -1 on timeout, -2 if closed
  int Push(const char* data, size_t size, double timeout_s) {
    std::unique_lock<std::mutex> lk(mu_);
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::duration<double>(timeout_s);
    while (q_.size() >= capacity_ && !closed_) {
      if (not_full_.wait_until(lk, deadline) == std::cv_status::timeout)
        return -1;
    }
    if (closed_) return -2;
    // malloc(1) floor: a non-null pointer even for empty payloads, so Pop's
    // nullptr return unambiguously means timeout/closed
    char* copy = static_cast<char*>(::malloc(size ? size : 1));
    if (!copy) return -3;  // out of host memory — surface, don't segfault
    if (size) ::memcpy(copy, data, size);
    q_.push_back({copy, size});
    not_empty_.notify_one();
    return 0;
  }

  // returns malloc'd block (caller frees via pdtpu_block_free); nullptr on
  // timeout/closed-empty. *size receives the length.
  char* Pop(size_t* size, double timeout_s, int* status) {
    std::unique_lock<std::mutex> lk(mu_);
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::duration<double>(timeout_s);
    while (q_.empty() && !closed_) {
      if (not_empty_.wait_until(lk, deadline) == std::cv_status::timeout) {
        *status = -1;
        return nullptr;
      }
    }
    if (q_.empty()) {  // closed and drained
      *status = -2;
      return nullptr;
    }
    Block b = q_.front();
    q_.pop_front();
    not_full_.notify_one();
    *size = b.size;
    *status = 0;
    return b.data;
  }

  void Close() {
    std::lock_guard<std::mutex> lk(mu_);
    closed_ = true;
    not_empty_.notify_all();
    not_full_.notify_all();
  }

  size_t Size() {
    std::lock_guard<std::mutex> lk(mu_);
    return q_.size();
  }

 private:
  size_t capacity_;
  bool closed_ = false;
  std::deque<Block> q_;
  std::mutex mu_;
  std::condition_variable not_empty_, not_full_;
};

}  // namespace

// ---------------------------------------------------------------------------
// C ABI
// ---------------------------------------------------------------------------

extern "C" {

void* pdtpu_store_server_create() { return new StoreServer(); }

int pdtpu_store_server_start(void* h, const char* host, int port) {
  return static_cast<StoreServer*>(h)->Start(host, port);
}

void pdtpu_store_server_destroy(void* h) {
  delete static_cast<StoreServer*>(h);
}

void* pdtpu_queue_create(size_t capacity) {
  return new BlockingQueue(capacity);
}

int pdtpu_queue_push(void* h, const char* data, size_t size,
                     double timeout_s) {
  return static_cast<BlockingQueue*>(h)->Push(data, size, timeout_s);
}

char* pdtpu_queue_pop(void* h, size_t* size, double timeout_s, int* status) {
  return static_cast<BlockingQueue*>(h)->Pop(size, timeout_s, status);
}

void pdtpu_queue_close(void* h) { static_cast<BlockingQueue*>(h)->Close(); }

size_t pdtpu_queue_size(void* h) {
  return static_cast<BlockingQueue*>(h)->Size();
}

void pdtpu_queue_destroy(void* h) { delete static_cast<BlockingQueue*>(h); }

void pdtpu_block_free(char* p) { ::free(p); }

// Stack n equal-sized sample buffers into dst (the np.stack hot path).
// Called through ctypes ⇒ GIL is released for the whole copy.
void pdtpu_collate_stack(char* dst, const char** srcs, size_t n,
                         size_t sample_bytes) {
  for (size_t i = 0; i < n; ++i)
    ::memcpy(dst + i * sample_bytes, srcs[i], sample_bytes);
}

}  // extern "C"
