// ddstore.cpp — distributed in-memory sample store (C++), TPU-era DDStore.
//
// Reference behavior being re-provided (call-site semantics of the C++
// pyddstore/DDStore library, see SURVEY.md §2.5 and
// hydragnn/utils/datasets/distdataset.py:22-183): each process registers a
// local shard of named variable-length arrays (`add`), any process fetches
// any global sample (`get`), with epoch fencing (`epoch_begin/epoch_end`)
// and teardown (`free`).
//
// Re-design: instead of MPI one-sided windows, a plain TCP data plane over
// DCN — each process runs a serving thread; gets are request/response with
// a per-connection mutex. Peer addresses are exchanged out-of-band (the
// Python layer passes the full peer list; on TPU pods that comes from
// jax.distributed). Local-shard gets short-circuit to memcpy.
//
// Build: datasets/ddstore.py compiles this on first use into
// libddstore-<source hash>.so (g++ -O2 -std=c++17 -shared -fPIC -lpthread)
//
// C ABI (ctypes-friendly):
//   dds_init(rank, world) -> handle
//   dds_listen(h, port) -> actual port
//   dds_connect(h, peer_rank, host, port) -> 0/err
//   dds_add(h, name, data, nbytes, counts, ncounts, itemsize)
//   dds_total(h, name) -> global sample count registered locally
//   dds_get(h, name, global_idx, out, out_cap) -> nbytes or -1
//   dds_epoch_begin(h) / dds_epoch_end(h)
//   dds_free(h)

#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <cstdint>
#include <cstring>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

struct Shard {
  std::vector<char> data;           // concatenated samples
  std::vector<int64_t> offsets;     // nsamples+1 byte offsets
  int64_t base = 0;                 // global index of first local sample
  int64_t global_total = 0;
};

struct Request {
  uint32_t name_len;
  int64_t index;
};

struct Store {
  int rank = 0;
  int world = 1;
  std::map<std::string, Shard> vars;
  std::mutex vars_mu;
  // data plane
  int listen_fd = -1;
  std::thread server;
  std::atomic<bool> running{false};
  std::vector<int> peer_fds;        // world entries, -1 if not connected
  std::vector<std::mutex> *peer_mu = nullptr;
  std::atomic<int64_t> epoch{0};
};

ssize_t read_full(int fd, void *buf, size_t n) {
  size_t got = 0;
  while (got < n) {
    ssize_t r = ::read(fd, (char *)buf + got, n - got);
    if (r <= 0) return -1;
    got += r;
  }
  return (ssize_t)got;
}

ssize_t write_full(int fd, const void *buf, size_t n) {
  size_t put = 0;
  while (put < n) {
    ssize_t r = ::write(fd, (const char *)buf + put, n - put);
    if (r <= 0) return -1;
    put += r;
  }
  return (ssize_t)put;
}

void serve_conn(Store *s, int fd) {
  for (;;) {
    Request req;
    if (read_full(fd, &req, sizeof(req)) < 0) break;
    std::string name(req.name_len, '\0');
    if (read_full(fd, name.data(), req.name_len) < 0) break;
    int64_t nbytes = -1;
    std::vector<char> payload;  // copied under the lock: dds_add may swap
                                // the shard buffers while we stream
    {
      std::lock_guard<std::mutex> g(s->vars_mu);
      auto it = s->vars.find(name);
      if (it != s->vars.end()) {
        Shard &sh = it->second;
        int64_t local = req.index - sh.base;
        if (local >= 0 && local + 1 < (int64_t)sh.offsets.size()) {
          nbytes = sh.offsets[local + 1] - sh.offsets[local];
          payload.assign(sh.data.begin() + sh.offsets[local],
                         sh.data.begin() + sh.offsets[local + 1]);
        }
      }
    }
    if (write_full(fd, &nbytes, sizeof(nbytes)) < 0) break;
    if (nbytes > 0 && write_full(fd, payload.data(), (size_t)nbytes) < 0)
      break;
  }
  ::close(fd);
}

void server_loop(Store *s) {
  while (s->running.load()) {
    sockaddr_in addr;
    socklen_t alen = sizeof(addr);
    int fd = ::accept(s->listen_fd, (sockaddr *)&addr, &alen);
    if (fd < 0) continue;
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    std::thread(serve_conn, s, fd).detach();
  }
}

}  // namespace

extern "C" {

void *dds_init(int rank, int world) {
  Store *s = new Store();
  s->rank = rank;
  s->world = world;
  s->peer_fds.assign(world, -1);
  s->peer_mu = new std::vector<std::mutex>(world);
  return s;
}

int dds_listen(void *h, int port) {
  Store *s = (Store *)h;
  s->listen_fd = ::socket(AF_INET, SOCK_STREAM, 0);
  int one = 1;
  setsockopt(s->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = INADDR_ANY;
  addr.sin_port = htons(port);
  if (::bind(s->listen_fd, (sockaddr *)&addr, sizeof(addr)) < 0) return -1;
  if (::listen(s->listen_fd, 64) < 0) return -1;
  socklen_t alen = sizeof(addr);
  getsockname(s->listen_fd, (sockaddr *)&addr, &alen);
  s->running = true;
  s->server = std::thread(server_loop, s);
  return ntohs(addr.sin_port);
}

int dds_connect(void *h, int peer, const char *host, int port) {
  Store *s = (Store *)h;
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(port);
  if (inet_pton(AF_INET, host, &addr.sin_addr) <= 0) return -1;
  if (::connect(fd, (sockaddr *)&addr, sizeof(addr)) < 0) return -1;
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  s->peer_fds[peer] = fd;
  return 0;
}

// counts: per-sample first-dim counts; itemsize: bytes per first-dim row
void dds_add(void *h, const char *name, const char *data, int64_t nbytes,
             const int64_t *counts, int64_t ncounts, int64_t itemsize,
             int64_t global_base, int64_t global_total) {
  Store *s = (Store *)h;
  Shard sh;
  sh.data.assign(data, data + nbytes);
  sh.offsets.resize(ncounts + 1);
  sh.offsets[0] = 0;
  for (int64_t i = 0; i < ncounts; ++i)
    sh.offsets[i + 1] = sh.offsets[i] + counts[i] * itemsize;
  sh.base = global_base;
  sh.global_total = global_total;
  std::lock_guard<std::mutex> g(s->vars_mu);
  s->vars[name] = std::move(sh);
}

int64_t dds_get(void *h, const char *name, int64_t index, int owner,
                char *out, int64_t out_cap) {
  Store *s = (Store *)h;
  // local fast path
  {
    std::lock_guard<std::mutex> g(s->vars_mu);
    auto it = s->vars.find(name);
    if (it != s->vars.end()) {
      Shard &sh = it->second;
      int64_t local = index - sh.base;
      if (local >= 0 && local + 1 < (int64_t)sh.offsets.size()) {
        int64_t nb = sh.offsets[local + 1] - sh.offsets[local];
        if (nb > out_cap) return -2;
        memcpy(out, sh.data.data() + sh.offsets[local], (size_t)nb);
        return nb;
      }
    }
  }
  if (owner < 0 || owner >= s->world) return -1;
  int fd = s->peer_fds[owner];
  if (fd < 0) return -1;
  std::lock_guard<std::mutex> g((*s->peer_mu)[owner]);
  Request req{(uint32_t)strlen(name), index};
  if (write_full(fd, &req, sizeof(req)) < 0) return -1;
  if (write_full(fd, name, req.name_len) < 0) return -1;
  int64_t nb;
  if (read_full(fd, &nb, sizeof(nb)) < 0) return -1;
  if (nb < 0) return -1;
  if (nb > out_cap) {
    // drain the payload so the connection stays framed for the next request
    char sink[4096];
    int64_t left = nb;
    while (left > 0) {
      size_t chunk = left > (int64_t)sizeof(sink) ? sizeof(sink) : (size_t)left;
      if (read_full(fd, sink, chunk) < 0) return -1;
      left -= chunk;
    }
    return -2;
  }
  if (read_full(fd, out, (size_t)nb) < 0) return -1;
  return nb;
}

void dds_epoch_begin(void *h) { ((Store *)h)->epoch++; }
void dds_epoch_end(void *h) {}

void dds_free(void *h) {
  Store *s = (Store *)h;
  s->running = false;
  if (s->listen_fd >= 0) {
    ::shutdown(s->listen_fd, SHUT_RDWR);
    ::close(s->listen_fd);
  }
  if (s->server.joinable()) s->server.join();
  for (int fd : s->peer_fds)
    if (fd >= 0) ::close(fd);
  delete s->peer_mu;
  delete s;
}

}  // extern "C"
